"""Device time of the walk's counting (``pixie.walk.count``: the dense
visit counts and the early-stop tally of each chunk) in the traced window,
per batch dispatched."""

from pixiebench import stages


def read(run):
    return stages.device_ms_per_batch(run, "pixie.walk.count")
