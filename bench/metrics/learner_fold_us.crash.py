"""Device time per scan turn, in the crash cell's faulty body, of the learner
fold (the conditional that records clean completions and refreshes mu_hat),
in microseconds: the ops whose innermost scope is ``rosella.learner_fold``
(``bench/recovery_stages.py``)."""
from bench import recovery_stages


def read(ctx):
    return recovery_stages.stage_us(ctx, "learner_fold")
