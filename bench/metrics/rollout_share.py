"""Share (%) of the window spent in the rollout stage: the union of the
benchmark's spans around ``task_fns["rollout"]``, over the window."""
from bench.metrics._shares import stage_share


def read(ctx):
    return stage_share(ctx, "rollout")
