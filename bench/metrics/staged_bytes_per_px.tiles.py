"""Bytes of request data the service moved between host and device per
pixel answered, in the tile cells: what it put on the device from
submit through the finalize, plus what it read back before delivery
(``Service.stats()`` totals ``h2d_bytes`` + ``d2h_bytes``, over
``pixels``).  Read only in a run whose trace saw the device: off the
chip no bus lies between the two.  A program without those counters
reads nothing."""
from bench import program_trace


def read(run):
    totals = run.stats["totals"]
    if (not program_trace.on_device(run) or "h2d_bytes" not in totals
            or not totals.get("pixels")):
        return None
    return (totals["h2d_bytes"] + totals["d2h_bytes"]) / totals["pixels"]
