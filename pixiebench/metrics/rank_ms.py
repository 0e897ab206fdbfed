"""Device time of stage 2 (``pixie.rank``: candidate fans, the two
embedding bags and the scenario heads) in the traced window, per batch
dispatched.  Only a ranked configuration runs it."""

from pixiebench import stages


def read(run):
    return stages.device_ms_per_batch(run, "pixie.rank")
