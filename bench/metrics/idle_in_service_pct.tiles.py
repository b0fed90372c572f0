"""Share of the traced window in which the device was idle while the
innermost open host span was one of the service's own (``serve.*``:
staging, dispatch, waiting, demux), in percent, in the tile cells
(``bench/program_trace.py``).  With the idle time in ``bench.*`` spans
and outside any span it makes up ``device_idle_pct.tiles``.  A program
with no ``serve.*`` span reads nothing."""
from bench import program_trace


def read(run):
    if not program_trace.on_device(run):
        return None
    red = program_trace.reduced(run)
    if not red["program_spans"]:
        return None
    return red["idle_pct"]["serve"]
