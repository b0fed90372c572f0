"""Device milliseconds of every operation that is not a Pallas kernel,
per megapixel answered in the traced window.  In the tile cells these
are the scheduler's XLA steps around the kernels
(``kernels/ops.py:_drive_scheduler``): above all the compaction's patch
gathers (``_gather_patches``, ``kind=kCustom`` fusions in the trace),
then its scatters, flags and copies."""


def read(run):
    if run.trace is None or not run.answered:
        return None
    t = run.trace["xla_s"]
    mpx = sum(s.pixels for s in run.answered) / 1e6
    return t * 1e3 / mpx if t > 0 else None
