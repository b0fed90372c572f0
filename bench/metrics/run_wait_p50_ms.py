"""Median time from a request's batch being enqueued on the device to
the host seeing its outputs ready (``Ticket.t_ready -
Ticket.t_dispatch``, on the service's clock), over the requests
answered in the window: the device's run plus the wait behind the batch
ahead and for the host to come round to draining it; in a run whose
trace saw the device.  A program whose tickets carry no ``t_ready``
reads nothing."""
from bench import load, program_trace


def read(run):
    if not program_trace.on_device(run):
        return None
    waits = [(s.ticket.t_ready - s.ticket.t_dispatch) * 1e3
             for s in run.answered
             if getattr(s.ticket, "t_ready", None) is not None]
    return load.percentile(waits, 50) if waits else None
