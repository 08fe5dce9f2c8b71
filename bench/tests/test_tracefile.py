"""The trace reduction, on a trace recorded on a TPU v5e and on hand-made events."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import measure, tracefile

FIXTURE = Path(__file__).parent / "fixtures" / "v5e_probe_trace.json"
SPANS = ["host_span_mm", "host_span_loop", "host_span_sleep"]


@pytest.fixture(scope="module")
def probe():
    fx = json.loads(FIXTURE.read_text())
    return fx, tracefile.reduce(fx["events"], tuple(fx["window"]), SPANS)


def _timeline_busy(events, w0, w1):
    """Busy time by brute force on a 1 ns grid (independent of the union code)."""
    t = np.zeros(int(w1 - w0) + 1, bool)
    for plane, line, name, s, d in events:
        if line != "XLA Ops" or tracefile.CONTAINER.match(tracefile.op_name(name)):
            continue
        a, b = max(s, w0) - w0, min(s + d, w1) - w0
        if b > a:
            t[int(a):int(b)] = True
    return t.sum() / 1e9


def test_busy_matches_brute_force(probe):
    fx, red = probe
    w0, w1 = fx["window"]
    assert red.window_s == pytest.approx((w1 - w0) / 1e9)
    assert red.busy_s[0] == pytest.approx(_timeline_busy(fx["events"], w0, w1), abs=2e-8)


def test_per_program_time(probe):
    fx, red = probe
    # whole calls inside the window only: the first matmul started before it
    assert red.calls["probe_mm"] == pytest.approx([102583e-9, 102527e-9])
    assert red.per_call_s("probe_loop") == pytest.approx((33000 + 33323 + 32999) / 3 * 1e-9)


def test_idle_goes_to_the_sleep(probe):
    fx, red = probe
    idle = dict(red.idle_by_span)
    assert max(idle, key=idle.get) == "host_span_sleep"
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s[0])
    assert red.top_ops[0][0] == "fusion" and len(red.busy_s) == 1


def ev(dev, name, s, d, line="XLA Ops"):
    return [f"/device:TPU:{dev}", line, name, s, d]


def test_collectives_exposed_and_gaps_by_span():
    events = [
        ev(0, "%fusion.1 = f32[8] fusion()", 0, 100),
        ev(0, "%all-gather.2 = f32[8] all-gather()", 50, 100),   # 50 hidden, 50 exposed
        ev(0, "%while.3 = (s32[]) while()", 0, 400),             # container: not counted
        ev(0, "%reduce-scatter.4 = f32[8] reduce-scatter()", 300, 20),
        ev(0, "jit_step(123)", 0, 320, line="XLA Modules"),
        ev(1, "%fusion.1 = f32[8] fusion()", 0, 200),
        ["/host:CPU", "host", "decode_token", 150, 100],
        ["/host:CPU", "host", "wait_request", 0, 1000],
    ]
    red = tracefile.reduce(events, (0, 1000), ["decode_token", "wait_request"])
    assert red.busy_s == pytest.approx([170e-9, 200e-9])
    assert red.collective_exposed_s == pytest.approx([70e-9, 0.0])
    assert red.calls == {"step": [pytest.approx(320e-9)]}
    # gaps on device 0: [150, 300) is covered by decode_token for 100 of 150,
    # [320, 1000) only by wait_request
    assert dict(red.idle_by_span) == pytest.approx(
        {"decode_token": 150e-9, "wait_request": 680e-9})


def test_interval_helpers():
    assert measure.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert measure.subtract([(0, 10)], [(2, 3), (5, 7)]) == 7
    assert measure.merge([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert measure.percentile([1, 2, 3, float("inf")], 0.95) == float("inf")
    assert measure.percentile([4, 1, 3, 2], 0.5) == 2
