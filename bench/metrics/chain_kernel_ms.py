"""Device milliseconds of Pallas kernels per request answered in the
traced window.  In the chain cells the only Pallas kernel is the row-band
geodesic chain kernel (``kernels/geodesic_chain.py``); a TPU trace names
it by its HLO custom call (target ``tpu_custom_call``), not by the
kernel's function."""


def read(run):
    if run.trace is None or not run.answered:
        return None
    t = run.trace["pallas_s"]
    return t * 1e3 / len(run.answered) if t > 0 else None
