"""Device time of the walk's hops (``pixie.walk.hop``: the pin -> board ->
pin steps of each chunk) in the traced window, per batch dispatched."""

from pixiebench import stages


def read(run):
    return stages.device_ms_per_batch(run, "pixie.walk.hop")
