"""Device time per call of the app's decode program (ms), from the trace."""


def read(ctx):
    t = ctx.reduction.per_call_s("serve_decode")
    return None if t is None else 1000.0 * t
