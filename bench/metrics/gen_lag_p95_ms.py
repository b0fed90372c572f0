"""95th percentile of how late the load generator called ``submit``
behind each request's due time (the generator and the service share one
host thread, so a long engine turn delays the next submissions)."""
from bench import load


def read(run):
    if not run.sent:
        return None
    return load.percentile([s.lag * 1e3 for s in run.sent], 95)
