"""Device time per step in all-gather, reduce-scatter, all-reduce and other
collectives during which no other op ran on that device (ms), averaged over
the chips."""


def read(ctx):
    red = ctx.reduction
    calls = red.calls.get("train_step")
    if not calls:
        return None
    exposed = sum(red.collective_exposed_s) / len(red.collective_exposed_s)
    return 1000.0 * exposed / len(calls)
