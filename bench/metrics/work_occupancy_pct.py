"""Share of the scheduler chunks a batch held its slots for that went to
images still converging (``Service.stats()`` totals
``work_occupancy``), in percent."""


def read(run):
    totals = run.stats["totals"]
    if not totals["busy_chunks"]:
        return None
    return 100.0 * totals["work_occupancy"]
