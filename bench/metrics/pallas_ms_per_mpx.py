"""Device milliseconds of the Pallas kernels (the scheduler's tile and
compact reconstruction kernels, ``kernels/geodesic_chain.py``) per
megapixel answered in the traced window."""


def read(run):
    if run.trace is None or not run.answered:
        return None
    t = run.trace["pallas_s"]
    mpx = sum(s.pixels for s in run.answered) / 1e6
    return t * 1e3 / mpx if t > 0 else None
