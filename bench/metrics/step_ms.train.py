"""Device time per call of the train step program (ms), from the trace."""


def read(ctx):
    t = ctx.reduction.per_call_s("train_step")
    return None if t is None else 1000.0 * t
