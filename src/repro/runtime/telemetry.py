"""What the runtime measures about itself: host spans on the profiler's
timeline, and the bounded sample windows behind the ``stats()`` percentiles.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation`` while a
profiler trace is being recorded, and a shared no-op context otherwise: no
object is made and no string is built.  jax is never imported here; a
process that has not imported it cannot be recording a trace, so the runtime
imports and runs without it.  The metadata (a request's stream key, a
batch's sequence number) shows in the trace viewer beside the span.

Spans: ``stream.send`` (serialize, publish, enqueue), ``stream.recv``
(fetch and deserialize an event that has arrived), ``serve.take_batch``,
``serve.model_fn`` and ``serve.emit`` (resolving a batch's futures, which
sends the replies of attached streams).
"""

from __future__ import annotations

import contextlib
import sys
from typing import Any, Sequence

#: Samples kept per latency window (per topic, per server statistic).
SAMPLE_WINDOW = 4096

_OFF = contextlib.nullcontext()


def span(name: str, **meta: Any) -> contextlib.AbstractContextManager:
    """A host span ``name`` with ``meta`` on the trace being recorded, if any."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None or not profiler.TraceAnnotation.is_enabled():
        return _OFF
    return profiler.TraceAnnotation(name, **meta)


def percentile(samples: Sequence[float], q: float) -> float:
    """Nearest-index ``q``-quantile of ``samples`` (0.0 when empty)."""
    if not samples:
        return 0.0
    xs = sorted(samples)
    idx = min(len(xs) - 1, max(0, round(q * (len(xs) - 1))))
    return xs[idx]


def mean(samples: Sequence[float]) -> float:
    return sum(samples) / len(samples) if samples else 0.0
