"""Process start to the first timed request: imports, graph generation,
compilation or cache load, and warm-up."""


def read(run):
    return float(run.setup_s)
