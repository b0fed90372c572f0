"""Share of the traced window in which no operation ran on the device,
in percent, in the frame-stream cells (profiler trace)."""
from bench import trace


def read(run):
    return trace.idle_pct(run.trace)
