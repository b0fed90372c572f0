"""Compaction gathers the scheduler ran per request: each compact chunk
gathers the marker's patches, and those whose active cell set moved
gather the mask's again (``Service.stats()`` totals ``compact_chunks``
plus ``mask_gathers``, over ``requests``)."""


def read(run):
    totals = run.stats["totals"]
    if "compact_chunks" not in totals or not totals["requests"]:
        return None
    return ((totals["compact_chunks"] + totals["mask_gathers"])
            / totals["requests"])
