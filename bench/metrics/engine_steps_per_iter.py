"""Rollout engine steps per iteration: the program's ``engine-step``
spans (``serve/engine.py``) in the window, over the window's whole
iterations."""


def read(ctx):
    steps = sum(1 for n, _, _, _ in ctx["program_spans"] if n == "engine-step")
    if not steps or not ctx["iterations"]:
        return None
    return steps / ctx["iterations"]
