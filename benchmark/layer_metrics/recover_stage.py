"""Device time of the recover program's named stages (the ``recover.*``
scopes of ``go_ibft_tpu/ops``), read from this run's device trace by
``benchmark/lib/stage_reduce.py``.  The metric's ``read`` names the stages
to sum (microseconds a padded lane, the denominator of
``recover_us_per_lane``) or a ``share`` of the program's busy time in %."""

from benchmark.lib import stage_reduce


def read(ctx, spec):
    return stage_reduce.metric(stage_reduce.stages_of_run(ctx), spec)
