"""Host time of ``PixieServer.pump`` calls that dispatched, per batch
dispatched: batch formation, argument transfer and the enqueue of the
jitted serving step."""


def read(run):
    spans = [(t1 - t0, n) for t0, t1, n in run.pumps if n]
    batches = sum(n for _, n in spans)
    if not batches:
        return None
    return 1e3 * sum(d for d, _ in spans) / batches
