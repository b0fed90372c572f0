"""Median latency of every request due in the window, from when it was
due to when its answer was ready; a failed request counts as missing."""
from bench import load


def read(run):
    return load.percentile(load.latencies_ms(run.sent), 50)
