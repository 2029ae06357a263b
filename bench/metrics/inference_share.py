"""Share (%) of the window spent in the inference stage: the union of the
benchmark's spans around ``task_fns["inference"]``, over the window."""
from bench.metrics._shares import stage_share


def read(ctx):
    return stage_share(ctx, "inference")
