"""Median time a request answered in the window waited in its bucket:
from admission to being popped into a batch (``Ticket.t_launch -
Ticket.t_enqueue``, on the service's clock), in a run whose trace saw
the device.  A program whose tickets carry no ``t_launch`` reads
nothing."""
from bench import load, program_trace


def read(run):
    if not program_trace.on_device(run):
        return None
    waits = [(s.ticket.t_launch - s.ticket.t_enqueue) * 1e3
             for s in run.answered
             if getattr(s.ticket, "t_launch", None) is not None]
    return load.percentile(waits, 50) if waits else None
