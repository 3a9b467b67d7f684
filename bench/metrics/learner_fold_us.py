"""Device time per scan turn of the learner fold (the conditional that records
completions and refreshes mu_hat), in microseconds: the ops whose innermost
scope is ``rosella.learner_fold`` (``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_us(ctx, "learner_fold")
