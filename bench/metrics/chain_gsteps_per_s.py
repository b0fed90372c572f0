"""Elementary 3x3 geodesic steps per second of the chain kernel's
device time, in billions: chain length x frame pixels x requests
answered in the traced window, over the Pallas kernels' time there (in
the chain cells the row chain kernel is the only one).  The plan runs
the last ``n mod fuse_k`` steps outside the kernel, so this overstates
the kernel's own rate by that share (28 of 1500 steps at n = 1500,
fuse_k = 32)."""


def read(run):
    if run.trace is None or not run.answered:
        return None
    t = run.trace["pallas_s"]
    if t <= 0:
        return None
    n = run.cell["params"]["n"]
    pixels = sum(s.pixels for s in run.answered)
    return n * pixels / t / 1e9
