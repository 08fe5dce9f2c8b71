"""Streaming data plane + continuous-batching serving tests.

Covers the PR's tentpole semantics: produce/consume round trips on thread
and process/wire clusters, bounded-buffer backpressure, consumer-ack
exactly-once eviction through the RefLedger, EOS and mid-stream close
waking blocked consumers, the dynamic batcher's size/window semantics,
and admission-control shedding.
"""

from __future__ import annotations

import sys
import threading
import time
import uuid

import numpy as np
import pytest

from repro.api import ClusterSpec, ServeSpec, Session
from repro.runtime.client import LocalCluster
from repro.runtime.serving import ModelServer, ServerOverloaded
from repro.runtime.stream import (
    EndOfStream,
    StreamClosed,
    StreamHub,
)
from repro.runtime.transfer import ResultStore


def _store() -> ResultStore:
    uid = uuid.uuid4().hex[:8]
    return ResultStore(
        {
            "name": f"stream-{uid}",
            "connector": {"connector_type": "memory", "segment": f"stream-{uid}"},
            "serializer": "default",
            "cache_size": 0,
        }
    )


@pytest.fixture
def hub():
    h = StreamHub(_store())
    yield h
    h.close()


# -- produce/consume round trips ------------------------------------------------


def test_round_trip_inproc(hub):
    prod = hub.producer("t")
    cons = hub.consumer("t")
    arrays = [np.arange(1024, dtype=np.float64) * i for i in range(10)]
    for i, a in enumerate(arrays):
        prod.send(a, metadata={"i": i})
    prod.close()
    items = list(cons)
    assert [it.metadata["i"] for it in items] == list(range(10))
    for it, a in zip(items, arrays):
        np.testing.assert_array_equal(it.value, a)
    stats = hub.stats()
    assert stats["events"] == 10
    assert stats["live_refs"] == 0  # auto-ack released everything
    # The broker carried metadata-sized events, not the payload bytes.
    assert stats["payload_bytes"] > 10 * 8000
    assert stats["broker_bytes"] < stats["payload_bytes"] / 4


def test_round_trip_session_thread_cluster(cluster):
    with Session(cluster=cluster) as session:
        prod = session.stream_producer("topic")
        cons = session.stream_consumer("topic")
        for i in range(5):
            prod.send({"seq": i, "blob": b"x" * 2048}, metadata={"seq": i})
        prod.close()
        got = [it.value["seq"] for it in cons]
        assert got == list(range(5))
        assert cluster.streams().stats()["live_refs"] == 0


@pytest.mark.parametrize("transport", ["inproc", "tcp"])
def test_round_trip_wire_broker(transport):
    """Clusters with a wire transport serve topics over a BrokerServer:
    the same semantics must hold across a real request/reply protocol."""
    with LocalCluster(n_workers=1, transport=transport) as cluster:
        hub = cluster.streams()
        prod = hub.producer("w")
        cons = hub.consumer("w")
        payload = np.arange(4096, dtype=np.float64)
        for i in range(6):
            prod.send(payload * i, metadata={"i": i})
        prod.close()
        items = list(cons)
        assert [it.metadata["i"] for it in items] == list(range(6))
        np.testing.assert_array_equal(items[3].value, payload * 3)
        stats = hub.stats()
        assert stats["live_refs"] == 0
        assert stats["broker_bytes"] < stats["payload_bytes"] / 4


@pytest.mark.parametrize("transport", [None, "inproc", "tcp"])
def test_topic_delivery_counters(transport):
    """Each topic counts its deliveries and times them from the top of
    ``send`` to the return of ``recv``, on every broker substrate."""
    with LocalCluster(n_workers=1, transport=transport) as cluster:
        hub = cluster.streams()
        prod, cons = hub.producer("slow"), hub.consumer("slow")
        for i in range(5):
            prod.send(np.arange(256) * i)
        time.sleep(0.05)  # the items sit in the topic: that counts
        got = [cons.recv(timeout=5) for _ in range(5)]
        other = hub.producer("fast")
        fast = hub.consumer("fast")
        other.send(1)
        fast.recv(timeout=5)
        topics = hub.stats()["topics"]
        assert set(topics) == {"slow", "fast"}
        slow = topics["slow"]
        assert slow["delivered"] == 5 and len(got) == 5
        assert 50.0 <= slow["deliver_p50_ms"] <= slow["deliver_p95_ms"] < 5000.0
        assert slow["deliver_mean_ms"] >= 50.0
        assert topics["fast"]["delivered"] == 1
        assert 0.0 < topics["fast"]["deliver_mean_ms"] < slow["deliver_mean_ms"]
        hub.producer("never")
        assert "never" not in hub.stats()["topics"]


def test_delivery_counts_survive_competing_consumers(hub):
    """Consumers racing on one topic lose no delivery count."""
    prod = hub.producer("race", buffer=16)
    n, workers = 400, 8
    got = []

    def drain(cons):
        got.extend(1 for _ in cons)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=drain, args=(hub.consumer("race"),))
                   for _ in range(workers)]
        for t in threads:
            t.start()
        for i in range(n):
            prod.send(i)
        prod.close()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert len(got) == n and hub.stats()["topics"]["race"]["delivered"] == n


@pytest.mark.slow
def test_round_trip_process_cluster():
    """The process-cluster configuration: spawned interpreters, tcp
    control plane, file-connector store tier -- stream payloads ride the
    shared store while events cross the tcp broker."""
    with ClusterSpec(1, worker_kind="process", transport="tcp").build() as cluster:
        cluster.wait_for_workers(timeout=90)
        hub = cluster.streams()
        prod = hub.producer("p")
        cons = hub.consumer("p")
        for i in range(4):
            prod.send(np.full(2048, float(i)), metadata={"i": i})
        prod.close()
        items = list(cons)
        assert [it.metadata["i"] for it in items] == list(range(4))
        assert hub.stats()["live_refs"] == 0


def test_work_queue_competing_consumers(hub):
    """Concurrent consumers on one topic compete: each event is delivered
    to exactly one of them (what keeps ack-eviction exactly-once)."""
    prod = hub.producer("wq")
    c1 = hub.consumer("wq")
    c2 = hub.consumer("wq")
    keys = {prod.send(i) for i in range(10)}
    got = [c1.recv(timeout=5) for _ in range(5)]
    got += [c2.recv(timeout=5) for _ in range(5)]
    assert {it.key for it in got} == keys  # all items, no duplicates
    assert hub.stats()["live_refs"] == 0


# -- backpressure ---------------------------------------------------------------


def test_backpressure_blocks_producer(hub):
    prod = hub.producer("bp", buffer=2)
    prod.send(b"a")
    prod.send(b"b")
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        prod.send(b"c", timeout=0.4)
    assert time.monotonic() - t0 >= 0.35  # actually blocked on the full buffer
    # The timed-out send must not leak its published bytes.
    assert len(hub.ledger.live_refs()) == 2


def test_backpressure_releases_when_consumer_drains(hub):
    prod = hub.producer("bp2", buffer=2)
    cons = hub.consumer("bp2")
    sent = []

    def _consume():
        for _ in range(6):
            sent.append(cons.recv(timeout=10).value)

    t = threading.Thread(target=_consume, daemon=True)
    t.start()
    for i in range(6):  # 6 sends through a 2-deep buffer: must not time out
        prod.send(i, timeout=10)
    t.join(timeout=10)
    assert sent == list(range(6))


# -- ack-driven eviction --------------------------------------------------------


def test_manual_ack_evicts_exactly_once(hub):
    prod = hub.producer("ack")
    cons = hub.consumer("ack", auto_ack=False)
    prod.send(np.arange(512))
    item = cons.recv(timeout=5)
    assert hub.results.fetch(item.ref, item.nbytes) is not None  # still stored
    assert item.ack() is True  # first ack releases...
    assert item.ack() is False  # ...and only the first
    assert hub.ledger.release(item.ref) is False
    assert hub.results.fetch(item.ref, item.nbytes) is None  # bytes evicted
    assert hub.stats()["live_refs"] == 0


def test_consumer_close_releases_unacked(hub):
    prod = hub.producer("unacked")
    cons = hub.consumer("unacked", auto_ack=False)
    prod.send(b"payload-1")
    prod.send(b"payload-2")
    delivered = cons.recv(timeout=5)
    cons.close()  # one delivered-but-unacked, one still queued
    assert delivered.ack() is False  # close already released it
    # The queued item stays tracked until the hub goes down.
    assert len(hub.ledger.live_refs()) == 1
    hub.close()
    assert len(hub.ledger.live_refs()) == 0


# -- EOS + mid-stream close -----------------------------------------------------


def test_eos_after_queued_items(hub):
    prod = hub.producer("eos")
    cons = hub.consumer("eos")
    prod.send(1)
    prod.send(2)
    prod.close()  # EOS rides the queue behind the two items
    assert cons.recv(timeout=5).value == 1
    assert cons.recv(timeout=5).value == 2
    with pytest.raises(EndOfStream):
        cons.recv(timeout=5)
    with pytest.raises(EndOfStream):  # sticky
        cons.recv(timeout=5)
    with pytest.raises(StreamClosed):
        prod.send(3)  # closed producer refuses new sends


def test_eos_fans_out_to_all_consumers(hub):
    """EOS is topic state, not a competed-for work-queue event: every
    consumer on the topic observes EndOfStream after the items drain,
    not just the one that would have popped a marker."""
    prod = hub.producer("fan")
    c1 = hub.consumer("fan")
    c2 = hub.consumer("fan")
    prod.send(1)
    prod.send(2)
    prod.close()
    got = sorted([c1.recv(timeout=5).value, c2.recv(timeout=5).value])
    assert got == [1, 2]
    with pytest.raises(EndOfStream):
        c1.recv(timeout=5)
    with pytest.raises(EndOfStream):
        c2.recv(timeout=5)


def test_eos_fans_out_over_wire_broker():
    """Same fan-out across the BrokerServer request/reply protocol."""
    with LocalCluster(n_workers=1, transport="inproc") as cluster:
        hub = cluster.streams()
        prod = hub.producer("fanw")
        c1 = hub.consumer("fanw")
        c2 = hub.consumer("fanw")
        prod.send(b"only")
        prod.close()
        assert c1.recv(timeout=5).value == b"only"
        with pytest.raises(EndOfStream):
            c1.recv(timeout=5)
        with pytest.raises(EndOfStream):
            c2.recv(timeout=5)


def test_producer_close_prompt_with_full_buffer(hub):
    """EOS takes no buffer slot: closing against a full topic with no
    consumer must not wait out the send timeout."""
    prod = hub.producer("full", buffer=1)
    prod.send(b"x")
    t0 = time.monotonic()
    prod.close()
    assert time.monotonic() - t0 < 1.0
    # The queued item stays tracked until the hub releases it.
    assert len(hub.ledger.live_refs()) == 1


def test_flush_observes_wire_broker_depth():
    """flush() must see the real queue depth through the STREAM_DEPTH
    RPC on wire clusters -- not silently no-op like the old duck-typed
    inproc-only path."""
    with LocalCluster(n_workers=1, transport="inproc") as cluster:
        hub = cluster.streams()
        prod = hub.producer("fl")
        cons = hub.consumer("fl")
        prod.send(b"x")
        with pytest.raises(TimeoutError):
            prod.flush(timeout=0.4)  # nothing consuming: still buffered
        assert cons.recv(timeout=5).value == b"x"
        prod.flush(timeout=5)  # drained: returns promptly


def test_close_wakes_blocked_consumer(hub):
    cons = hub.consumer("idle")
    woke: list[BaseException] = []

    def _recv():
        try:
            cons.recv(timeout=30)
        except BaseException as exc:  # noqa: BLE001 - recording the wake
            woke.append(exc)

    t = threading.Thread(target=_recv, daemon=True)
    t.start()
    time.sleep(0.3)  # let it block
    cons.close()
    t.join(timeout=5)
    assert len(woke) == 1 and isinstance(woke[0], StreamClosed)


def test_hub_close_wakes_blocked_consumer():
    hub = StreamHub(_store())
    cons = hub.consumer("idle2")
    woke: list[BaseException] = []

    def _recv():
        try:
            cons.recv(timeout=30)
        except BaseException as exc:  # noqa: BLE001 - recording the wake
            woke.append(exc)

    t = threading.Thread(target=_recv, daemon=True)
    t.start()
    time.sleep(0.3)
    hub.close()
    t.join(timeout=5)
    assert len(woke) == 1 and isinstance(woke[0], StreamClosed)


def test_session_close_flushes_stream_endpoints():
    with LocalCluster(n_workers=1) as cluster:
        session = Session(cluster=cluster)
        prod = session.stream_producer("s")
        cons = session.stream_consumer("s", auto_ack=False)
        prod.send(b"x" * 1024)
        cons.recv(timeout=5)  # delivered, never acked
        session.close()
        assert prod.closed and cons.closed
        # The session released the unacked ref before the data plane went.
        assert len(cluster.streams().ledger.live_refs()) == 0


# -- the dynamic batcher --------------------------------------------------------


def test_full_batch_fires_before_window():
    sizes: list[int] = []

    def fn(batch):
        sizes.append(len(batch))
        return [x + 1 for x in batch]

    with ModelServer(fn, max_batch_size=4, max_wait_ms=5000.0) as server:
        t0 = time.monotonic()
        futs = [server.submit(i) for i in range(4)]
        assert [f.result(timeout=10) for f in futs] == [1, 2, 3, 4]
        # A full batch must not wait out the 5s window.
        assert time.monotonic() - t0 < 2.0
    assert sizes == [4]


def test_partial_batch_waits_the_window():
    sizes: list[int] = []

    def fn(batch):
        sizes.append(len(batch))
        return list(batch)

    with ModelServer(fn, max_batch_size=8, max_wait_ms=150.0) as server:
        t0 = time.monotonic()
        futs = [server.submit(i) for i in range(2)]
        assert [f.result(timeout=10) for f in futs] == [0, 1]
        elapsed = time.monotonic() - t0
    assert sizes == [2]  # both rode one batch...
    assert elapsed >= 0.10  # ...after the batcher waited out the window


def test_admission_control_sheds_when_full():
    started = threading.Event()
    release = threading.Event()

    def fn(batch):
        started.set()
        release.wait(timeout=30)
        return list(batch)

    server = ModelServer(fn, max_batch_size=1, max_wait_ms=1.0, queue_depth=2)
    try:
        first = server.submit("a")  # taken by the batcher, blocks in fn
        assert started.wait(timeout=10)
        server.submit("b")
        server.submit("c")  # queue now at depth
        with pytest.raises(ServerOverloaded):
            server.submit("d")  # shed, not queued
        stats = server.stats()
        assert stats["rejected"] == 1
        assert stats["pending"] == 2
        release.set()
        assert first.result(timeout=10) == "a"
        server.flush(timeout=10)
        assert server.stats()["served"] == 3
    finally:
        release.set()
        server.close()


def test_flush_not_fooled_by_sheds():
    """A shed must not let flush() return while the final batch is still
    inside model_fn: rejected submissions never enter ``_requests``, so
    counting them toward drain progress would close reply streams under
    in-flight responses (the served == n_req invariant under shedding)."""
    permits = threading.Semaphore(0)
    calls: list[list] = []

    def fn(batch):
        calls.append(list(batch))
        assert permits.acquire(timeout=30)
        return list(batch)

    server = ModelServer(fn, max_batch_size=1, max_wait_ms=1.0, queue_depth=1)
    try:
        fa = server.submit("a")
        deadline = time.monotonic() + 10
        while len(calls) < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        server.submit("b")  # queue now at depth
        with pytest.raises(ServerOverloaded):
            server.submit("c")  # shed: rejected=1
        permits.release()  # "a" completes; "b" becomes the in-flight batch
        assert fa.result(timeout=10) == "a"
        while len(calls) < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        # Queue empty, rejected=1, "b" in flight: the buggy drain check
        # (batched + rejected >= admitted) returned here.
        flushed = threading.Event()

        def _flush():
            server.flush(timeout=10)
            flushed.set()

        t = threading.Thread(target=_flush, daemon=True)
        t.start()
        assert not flushed.wait(timeout=0.4)  # "b" still inside model_fn
        permits.release()
        assert flushed.wait(timeout=10)
        assert server.stats()["served"] == 2
    finally:
        permits.release()
        permits.release()
        server.close()


def test_failed_batch_fails_requests_and_drains():
    def fn(batch):
        raise ValueError("model exploded")

    with ModelServer(fn, max_batch_size=2, max_wait_ms=1.0) as server:
        futs = [server.submit(i) for i in range(2)]
        for f in futs:
            with pytest.raises(ValueError, match="model exploded"):
                f.result(timeout=10)
        server.flush(timeout=5)  # failed batches still count as drained
        stats = server.stats()
        assert stats["batches"] >= 1 and stats["served"] == 2


def test_latency_percentiles_recorded():
    with ModelServer(lambda b: list(b), max_batch_size=4, max_wait_ms=1.0) as server:
        futs = [server.submit(i) for i in range(8)]
        [f.result(timeout=10) for f in futs]
        server.flush(timeout=10)
        stats = server.stats()
    assert stats["latency_p99_ms"] >= stats["latency_p50_ms"] >= 0.0
    assert stats["requests"] == 8 and stats["served"] == 8


# -- streams + server composed (the serving loop) -------------------------------


def test_attach_serves_request_stream(hub):
    server = ModelServer(
        lambda batch: [float(np.asarray(x).sum()) for x in batch],
        max_batch_size=4,
        max_wait_ms=5.0,
    )
    try:
        server.attach(hub.consumer("req"), hub.producer("resp"))
        prod = hub.producer("req")
        cons = hub.consumer("resp")
        sent = {}
        for i in range(6):
            key = prod.send(np.full(128, float(i)))
            sent[key] = 128.0 * i
        prod.close()  # EOS: pump flushes and closes the reply topic
        got = {
            it.metadata["key"]: it.value
            for it in cons
            if it.metadata["status"] == "ok"
        }
        assert got == sent
    finally:
        server.close()


def test_serve_spec_defaults_and_overrides():
    spec = ClusterSpec(
        n_workers=1, serve=ServeSpec(max_batch_size=3, max_wait_ms=7.0, queue_depth=9)
    )
    with Session(cluster=spec) as session:
        server = session.serve(lambda b: list(b))
        assert (server.max_batch_size, server.max_wait_ms, server.queue_depth) == (
            3,
            7.0,
            9,
        )
        override = session.serve(lambda b: list(b), max_batch_size=5)
        assert override.max_batch_size == 5
        assert override.max_wait_ms == 7.0  # non-overridden knobs keep spec values
    assert server._closed and override._closed  # session close stops servers
