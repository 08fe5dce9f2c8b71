"""Median wait of a request in the batcher's admission queue (ms), from
``ModelServer.stats()`` over the window's requests."""


def read(ctx):
    return ctx.counters.get("queue_p50_ms")
