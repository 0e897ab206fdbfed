"""Device time of every program run in the traced window, per batch
dispatched in it (no name matching: a renamed jit keeps the metric)."""


def read(run):
    if run.summary is None or not run.summary.n_devices or not run.batches:
        return None
    return run.summary.module_ns / run.batches / 1e6
