"""Device time of the top-k over the boosted counts (``pixie.topk``) in
the traced window, per batch dispatched."""

from pixiebench import stages


def read(run):
    return stages.device_ms_per_batch(run, "pixie.topk")
