"""Host time per step spent getting the next batch (ms): the app's span
around ``next(prefetcher)`` and the proxy's resolution, from the trace."""


def read(ctx):
    spans = ctx.reduction.host_spans.get("next_batch")
    return 1000.0 * sum(spans) / len(spans) if spans else None
