"""Share of the batch slots launched that held a real request
(``Service.stats()`` totals ``batch_occupancy``), in percent."""


def read(run):
    totals = run.stats["totals"]
    if not totals["batches"]:
        return None
    return 100.0 * totals["batch_occupancy"]
