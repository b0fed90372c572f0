"""Seconds from the start of ``bench/run.py`` to the window's start:
imports, the input pool, compiling or loading every program, warm-up."""


def read(run):
    return run.setup_s
