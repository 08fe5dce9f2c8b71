"""From the profiler's trace to device numbers.

``load`` reads an ``.xplane.pb`` into plain events, which is also the format
of the recorded fixture under ``bench/tests/fixtures``; ``reduce`` turns them
into what the per-layer metrics and the breakdown read:

- busy: the union of the intervals in which an XLA op ran on each device,
  inside the traced window (host and device share one clock in the trace);
- per jitted program: its device time per call, from the ``XLA Modules``
  line (a program named ``f`` appears as ``jit_f(<fingerprint>)``);
- collectives: device time in all-gather, reduce-scatter, all-reduce,
  all-to-all and collective-permute ops during which no other op ran;
- idle gaps: device-0 idle time, each gap given to the first host span of
  the app's precedence list that covers half of it or more, else to the span
  that covers most of it.  The device's clock in the trace runs about a
  millisecond ahead of the host's (measured on a v5e: a program's device start
  precedes the host span that dispatched it), so gaps shorter than that may
  land on the span next to the right one.
"""

from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Iterable, Sequence

from bench.measure import merge, subtract, union_length

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(r"all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute")
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")
MODULE = re.compile(r"^(jit_[A-Za-z0-9_]+)\(")


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def load(trace_dir: str, span_names: Iterable[str]) -> list[list]:
    """Events ``[plane, line, name, start_ns, dur_ns]`` of every device op,
    device program, and host span named in ``span_names``."""
    from jax.profiler import ProfileData

    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    wanted = set(span_names)
    events: list[list] = []
    for plane in ProfileData.from_file(files[0]).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    events.extend(
                        [plane.name, line.name, e.name, e.start_ns, e.duration_ns]
                        for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events.extend(
                    [plane.name, "host", e.name, e.start_ns, e.duration_ns]
                    for e in line.events if e.name in wanted
                )
    return events


@dataclass
class Reduction:
    window_s: float
    busy_s: list[float]                                   # per device
    calls: dict[str, list[float]] = field(default_factory=dict)   # program -> device s per call (device 0)
    collective_exposed_s: list[float] = field(default_factory=list)
    top_ops: list[list] = field(default_factory=list)     # [[op, s]] device 0
    idle_by_span: list[list] = field(default_factory=list)  # [[span, s]]
    host_spans: dict[str, list[float]] = field(default_factory=dict)  # name -> durations s

    def per_call_s(self, program: str) -> float | None:
        xs = self.calls.get(program)
        return sum(xs) / len(xs) if xs else None


def reduce(events: Sequence[Sequence], window: tuple[float, float],
           precedence: Sequence[str]) -> Reduction:
    """Reduce ``load``'s events over ``window`` (ns, the host span that
    brackets the traced slice)."""
    w0, w1 = window

    def clip(s, d):
        return max(s, w0), min(s + d, w1)

    ops, colls, modules = defaultdict(list), defaultdict(list), defaultdict(list)
    op_time: dict[str, float] = defaultdict(float)
    spans: dict[str, list[tuple[float, float]]] = defaultdict(list)
    for plane, line, name, s, d in events:
        if line == "host":
            if s < w1 and s + d > w0:
                spans[name].append((s, s + d))
            continue
        dev = int(DEVICE_PLANE.match(plane).group(1))
        if s >= w1 or s + d <= w0:
            continue
        if line == "XLA Modules":
            m = MODULE.match(name)
            if m and s >= w0 and s + d <= w1:     # whole calls only
                modules[dev].append((m.group(1)[4:], d / 1e9))
            continue
        op = op_name(name)
        if CONTAINER.match(op):
            continue
        iv = clip(s, d)
        (colls if COLLECTIVE.search(op) else ops)[dev].append(iv)
        if dev == 0:
            op_time[op] += (iv[1] - iv[0]) / 1e9
    devices = sorted(set(ops) | set(colls) | set(modules)) or [0]
    busy = [union_length(ops[d] + colls[d]) / 1e9 for d in devices]
    exposed = [subtract(colls[d], ops[d]) / 1e9 for d in devices]
    calls: dict[str, list[float]] = defaultdict(list)
    for prog, sec in modules.get(devices[0], []):
        calls[prog].append(sec)
    # idle gaps on device 0, each given to the first overlapping span
    busy0 = merge(ops[devices[0]] + colls[devices[0]])
    gaps, cur = [], w0
    for s, e in busy0:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < w1:
        gaps.append((cur, w1))
    merged = {name: merge(spans.get(name, ())) for name in precedence}
    starts = {name: [s for s, _ in iv] for name, iv in merged.items()}

    def overlap(name, g0, g1):
        iv, total = merged[name], 0.0
        i = max(0, bisect.bisect_right(starts[name], g0) - 1)
        while i < len(iv) and iv[i][0] < g1:
            total += max(0.0, min(iv[i][1], g1) - max(iv[i][0], g0))
            i += 1
        return total

    idle: dict[str, float] = defaultdict(float)
    for g0, g1 in gaps:
        cover = [(overlap(name, g0, g1), name) for name in precedence]
        half = [name for c, name in cover if c >= 0.5 * (g1 - g0)]
        best = max(cover, default=(0.0, "none"))
        owner = half[0] if half else (best[1] if best[0] > 0 else "none")
        idle[owner] += (g1 - g0) / 1e9
    return Reduction(
        window_s=(w1 - w0) / 1e9,
        busy_s=busy,
        calls=dict(calls),
        collective_exposed_s=exposed,
        top_ops=[[k, v] for k, v in sorted(op_time.items(), key=lambda kv: -kv[1])[:10]],
        idle_by_span=[[k, v] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])[:10]],
        host_spans={k: [(e - s) / 1e9 for s, e in v] for k, v in spans.items()},
    )
