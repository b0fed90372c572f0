"""Device milliseconds of the compaction's gathers per megapixel
answered in the traced window: the operations that
``Service.op_scopes()`` places in the ``compact_gather`` scope
(``kernels/ops.py:_gather_patches`` and ``_gather_mid``), found in the
trace by their HLO head (``bench/program_trace.py``)."""
from bench import program_trace


def read(run):
    red = program_trace.reduced(run, with_scopes=True)
    if red is None or not red["gather_s"] or not run.answered:
        return None
    mpx = sum(s.pixels for s in run.answered) / 1e6
    return red["gather_s"] * 1e3 / mpx
