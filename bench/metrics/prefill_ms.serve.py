"""Device time per call of the app's prefill program (ms), from the trace."""


def read(ctx):
    t = ctx.reduction.per_call_s("serve_prefill")
    return None if t is None else 1000.0 * t
