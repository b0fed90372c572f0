"""Scheduler chunks spent per request answered, over the run
(``Service.stats()`` totals ``busy_chunks`` / ``requests``)."""


def read(run):
    totals = run.stats["totals"]
    if not totals["busy_chunks"] or not totals["requests"]:
        return None
    return totals["busy_chunks"] / totals["requests"]
