"""Mean requests per batch as a share of ``max_batch_size`` (%), from
``ModelServer.stats()``."""


def read(ctx):
    mean = ctx.counters.get("mean_batch")
    return None if not mean else 100.0 * mean / ctx.counters["max_batch_size"]
