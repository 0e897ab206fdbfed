"""Share of the batch lanes dispatched in the window that held a real
request (``ServerStats.lanes_filled`` over ``lanes_dispatched``)."""

from pixiebench import stages


def read(run):
    return stages.counter_share(run, "lanes_filled", "lanes_dispatched")
