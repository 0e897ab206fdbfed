"""Host time of batch formation (the program's ``pixie.dispatch.form``
spans: NumPy batch assembly, key stacking, argument transfers, budgets)
inside the traced window, per batch dispatched."""

from pixiebench import stages


def read(run):
    return stages.span_ms_per_batch(run, "pixie.dispatch.form")
