"""95th percentile of the latency of every request due in the window,
from when it was due to when its answer was ready; a failed request
counts as missing.  A per-layer metric: on one chip a host stall of a
second or more, once or twice in six runs, moves it by several times,
so it is not steady enough to hold a bound end to end."""
from bench import load


def read(run):
    return load.percentile(load.latencies_ms(run.sent), 95)
