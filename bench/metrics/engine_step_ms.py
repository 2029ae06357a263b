"""Mean host time (ms) of one rollout engine step: the program's
``engine-step`` spans (``serve/engine.py``, which end after the sampled
tokens reach the host), total over count."""


def read(ctx):
    steps = [e - s for n, _, s, e in ctx["program_spans"] if n == "engine-step"]
    if not steps:
        return None
    return 1e3 * sum(steps) / len(steps)
