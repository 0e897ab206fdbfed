"""Walk steps taken over the Eq. 2 steps budgeted, summed over the requests
answered in the window (``ServerStats.steps_taken`` over
``steps_budgeted``): what early stopping left of the budget."""

from pixiebench import stages


def read(run):
    return stages.counter_share(run, "steps_taken", "steps_budgeted")
