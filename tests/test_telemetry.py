"""What the serving path measures about itself: host spans on a profiler
trace, and the request stages of ``ModelServer.stats()`` and the stream
topics, which add up to the latency a client sees."""

from __future__ import annotations

import glob
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.api import ClusterSpec, ServeSpec, Session
from repro.runtime import telemetry
from repro.runtime.serving import ModelServer


class _NoStrings:
    """Metadata that fails if anything formats it."""

    def __str__(self):
        raise AssertionError("formatted while the profiler is off")

    __repr__ = __format__ = __str__


def test_span_is_a_shared_noop_while_no_trace_records():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    s = telemetry.span("stream.send", key=_NoStrings())
    assert s is telemetry.span("serve.emit", batch=3)
    with s:
        pass


def test_span_needs_no_jax(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax")
    assert telemetry.span("stream.recv", key="k") is telemetry.span("other")


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, dict(e.stats)) for e in line.events]
    return out


def test_trace_shows_stream_and_batcher_spans(tmp_path):
    spec = ClusterSpec(n_workers=1, serve=ServeSpec(max_batch_size=2, max_wait_ms=1.0))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with Session(cluster=spec) as session:
            server = session.serve(lambda batch: [int(np.sum(x)) for x in batch])
            server.attach(session.stream_consumer("requests"),
                          session.stream_producer("responses"))
            requests = session.stream_producer("requests")
            responses = session.stream_consumer("responses")
            keys = [requests.send(np.full(4, i)) for i in range(3)]
            replies = [responses.recv(timeout=10) for _ in keys]
            requests.close()      # end of stream: the pump stops
    finally:
        jax.profiler.stop_trace()
    assert sorted(r.metadata["key"] for r in replies) == sorted(keys)
    events = _host_events(tmp_path)
    names = {n for n, _ in events}
    assert {"stream.send", "stream.recv", "serve.take_batch", "serve.model_fn",
            "serve.emit"} <= names
    sent = {st.get("key") for n, st in events if n == "stream.send"}
    assert set(keys) <= sent                          # each request's stream key
    batches = [st for n, st in events if n == "serve.model_fn"]
    assert {st["batch"] for st in batches} == set(range(len(batches)))
    assert sum(st["size"] for st in batches) == 3


def test_stage_means_add_up_to_client_latency():
    """Request hop, queue, service, emit and reply hop tile a request's path:
    their means add up to the mean latency the client measures."""
    n = 24
    spec = ClusterSpec(n_workers=1, serve=ServeSpec(max_batch_size=4, max_wait_ms=2.0))

    def model(batch):
        time.sleep(0.02)
        return [float(np.asarray(x).sum()) for x in batch]

    with Session(cluster=spec) as session:
        server = session.serve(model)
        server.attach(session.stream_consumer("requests"),
                      session.stream_producer("responses"))
        requests = session.stream_producer("requests")
        responses = session.stream_consumer("responses")
        sent, got = {}, {}

        def receive():
            for _ in range(n):
                item = responses.recv(timeout=30)
                got[item.metadata["key"]] = time.monotonic()

        receiver = threading.Thread(target=receive)
        receiver.start()
        for i in range(n):
            t = time.monotonic()
            sent[requests.send(np.full(64, i))] = t
            time.sleep(0.004)                      # arrivals faster than service
        receiver.join(timeout=30)
        server.flush()
        stats = server.stats()
        topics = session.cluster.streams().stats()["topics"]
        requests.close()

    assert sorted(got) == sorted(sent)
    client_ms = 1000.0 * sum(got[k] - sent[k] for k in sent) / n
    assert topics["requests"]["delivered"] == n and topics["responses"]["delivered"] == n
    stages = (topics["requests"]["deliver_mean_ms"], stats["queue_mean_ms"],
              stats["service_mean_ms"], stats["emit_mean_ms"],
              topics["responses"]["deliver_mean_ms"])
    assert stats["service_mean_ms"] >= 20.0 and stats["queue_mean_ms"] > 0.0
    assert sum(stages) == pytest.approx(client_ms, abs=1.0)
    assert stats["service_p50_ms"] >= 20.0 and stats["turnaround_p50_ms"] > 0.0


def test_turnaround_counts_only_batches_a_request_waited_for():
    def model(batch):
        time.sleep(0.005)
        return list(batch)

    with ModelServer(model, max_batch_size=1, max_wait_ms=0.0) as server:
        for i in range(3):                          # one at a time: none waits
            assert server.submit(i).result(timeout=10) == i
            time.sleep(0.02)
        assert server.stats()["turnaround_p50_ms"] == 0.0
        futs = [server.submit(i) for i in range(6)]  # a backlog: each waits
        assert [f.result(timeout=10) for f in futs] == list(range(6))
        server.flush()
        stats = server.stats()
    assert 0.0 < stats["turnaround_p50_ms"] < 5.0
    assert stats["emit_mean_ms"] >= 0.0
