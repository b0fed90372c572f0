"""Share of the traced window that the service's one host thread spent
blocked in ``jax.block_until_ready`` waiting for a batch: the
``serve.wait`` spans inside the window over the window, on the
profiler's clock (``bench/program_trace.py``), in percent.
``Service.stats()`` counts the same time as ``host_blocked_s``, but its
``span_s`` (first dispatch to last drain) is no denominator here: in a
traced run it runs on through the profiler's shutdown, which comes
before the harness drains the last batches.  A program with no
``serve.*`` span reads nothing."""
from bench import program_trace


def read(run):
    if not program_trace.on_device(run):
        return None
    red = program_trace.reduced(run)
    if not red["program_spans"] or "serve.wait" not in red["spans"]:
        return None
    return 100.0 * red["spans"]["serve.wait"][1] / red["window_s"]
