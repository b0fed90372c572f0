"""Megapixels answered correctly per second.  An open loop counts the
requests answered inside the window, over the window.  A closed loop
sends nothing once the window's time is up and waits for the requests
it has in flight: it counts every request sent in the window, over the
time from the window's start to the last answer."""
from bench import load


def read(run):
    return (load.completed_mpx(run.sent, run.t_close)
            / (run.t_close - run.t0))
