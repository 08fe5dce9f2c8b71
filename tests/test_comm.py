"""Transport conformance suite + live process-worker cluster tests.

The conformance half runs the same contract against every registered
transport (``inproc``, ``tcp``): ordering, big frames, concurrent
senders, close semantics (no hang-on-peer-death), timeouts, and byte
accounting including the control fast path.

The cluster half spins real spawned-interpreter workers over tcp:
submit/gather, error propagation, store-tier results, worker crash ->
lineage recovery, and ``worker_stats()`` telemetry over the wire.
"""

import threading
import time
import uuid

import numpy as np
import pytest

from repro.runtime import comm as C
from repro.runtime import messages as M
from repro.runtime.comm import (
    ChannelClosed,
    LocalChannel,
    PipeEndpoint,
    decode_message,
    encode_message,
    encode_message_frames,
    is_control,
)

# ---------------------------------------------------------------------------
# codec


def test_control_fast_path_encoding():
    msg = M.msg(M.HEARTBEAT, worker="w0", managed_bytes=123, state="running")
    blob = encode_message(msg)
    assert is_control(blob)
    tag, p = decode_message(blob)
    assert tag == M.HEARTBEAT
    assert p == {"worker": "w0", "managed_bytes": 123, "state": "running"}


def test_task_messages_take_general_path():
    # RUN_TASK payloads carry user args; tuples must round-trip exactly,
    # so they may never ride msgpack (which turns tuples into lists).
    msg = M.msg(M.RUN_TASK, key="k", args=(1, (2, 3)))
    blob = encode_message(msg)
    assert not is_control(blob)
    tag, p = decode_message(blob)
    assert tag == M.RUN_TASK
    assert p["args"] == (1, (2, 3))
    assert isinstance(p["args"], tuple)


def test_frames_concatenation_equals_blob():
    msg = ("x", {"arr": np.arange(1000, dtype=np.int64)})
    frames = encode_message_frames(msg)
    joined = b"".join(bytes(f) for f in frames)
    assert joined == bytes(encode_message(msg))
    tag, p = decode_message(joined)
    assert tag == "x"
    np.testing.assert_array_equal(p["arr"], np.arange(1000, dtype=np.int64))


def test_control_fast_path_counts_in_byte_counter():
    ch = LocalChannel("fast")
    a, b = ch.endpoint_a(), ch.endpoint_b()
    a.send(M.msg(M.HEARTBEAT, worker="w0"))
    a.send(("general", {"x": np.arange(8)}))
    b.recv(timeout=2)
    b.recv(timeout=2)
    snap_a, snap_b = a.counter.snapshot(), b.counter.snapshot()
    assert snap_a["sent_msgs"] == 2 and snap_a["fast_msgs"] == 1
    assert snap_b["recv_msgs"] == 2 and snap_b["fast_msgs"] == 1
    assert 0 < snap_a["fast_bytes"] < snap_a["sent_bytes"]


# ---------------------------------------------------------------------------
# transport conformance


@pytest.fixture(params=["inproc", "tcp", "tcp-compressed"])
def comm_pair(request):
    """A connected (client, server) comm pair over the given transport.

    The ``tcp-compressed`` variant runs the whole contract with an
    aggressive compression policy (tiny frame threshold), so ordering,
    close semantics, and byte accounting are asserted over envelopes too.
    """
    if request.param == "inproc":
        address = f"inproc://conf-{uuid.uuid4().hex[:8]}"
    else:
        address = "tcp://127.0.0.1:0"
    kwargs = {}
    if request.param == "tcp-compressed":
        kwargs["transfer"] = {"compression": "auto", "min_frame_bytes": 1024}
    accepted = []
    ready = threading.Event()

    def handler(comm):
        accepted.append(comm)
        ready.set()

    listener = C.listen(address, handler, **kwargs)
    client = C.connect(listener.address, **kwargs)
    assert ready.wait(5), "listener never accepted"
    server = accepted[0]
    yield client, server
    for comm in (client, server):
        try:
            comm.close()
        except Exception:
            pass
    listener.stop()


def test_send_recv_ordering(comm_pair):
    client, server = comm_pair
    for i in range(50):
        if i % 3 == 0:
            client.send(M.msg(M.HEARTBEAT, worker=f"w{i}", seq=i))
        else:
            client.send(("general", {"seq": i, "arr": np.arange(i + 1)}))
    for i in range(50):
        tag, p = server.recv(timeout=5)
        assert p["seq"] == i  # both shapes carry seq; order is FIFO


def test_bidirectional(comm_pair):
    client, server = comm_pair
    client.send(("ping", {"n": 1}))
    tag, p = server.recv(timeout=5)
    server.send(("pong", {"n": p["n"] + 1}))
    tag, p = client.recv(timeout=5)
    assert (tag, p["n"]) == ("pong", 2)


def test_big_frame_roundtrip_and_accounting(comm_pair):
    client, server = comm_pair
    arrs = {f"a{i}": np.random.default_rng(i).random(250_000) for i in range(4)}
    # Send from a thread: an 8MB message legitimately blocks a tcp sender
    # until the peer drains the socket (there is no peer pump in this test).
    sent = []
    sender = threading.Thread(target=lambda: sent.append(client.send(("blob", arrs))))
    sender.start()
    tag, p = server.recv(timeout=10)
    sender.join(timeout=10)
    assert sent and sent[0] > 2_000_000  # ~8MB of float64 in 4 frames
    assert tag == "blob"
    for k, v in arrs.items():
        np.testing.assert_array_equal(p[k], v)
    assert (
        client.counter.snapshot()["sent_bytes"]
        == server.counter.snapshot()["recv_bytes"]
    )


def test_compressed_tcp_saves_wire_bytes():
    """Compressible frames cross tcp smaller than logical, byte-identical."""
    from repro.core.compress import LINK_TCP, TransferLedger

    ledger = TransferLedger()
    transfer = {"compression": "auto", "min_frame_bytes": 1024}
    accepted = []
    ready = threading.Event()

    def handler(comm):
        accepted.append(comm)
        ready.set()

    listener = C.listen(
        "tcp://127.0.0.1:0", handler, transfer=transfer, ledger=ledger
    )
    client = C.connect(listener.address, transfer=transfer, ledger=ledger)
    assert ready.wait(5)
    server = accepted[0]
    try:
        arr = np.zeros(1_000_000, dtype=np.float64)  # 8 MiB of zero blocks
        sent = []
        t = threading.Thread(target=lambda: sent.append(client.send(("z", {"a": arr}))))
        t.start()
        tag, p = server.recv(timeout=10)
        t.join(timeout=10)
        assert tag == "z"
        np.testing.assert_array_equal(p["a"], arr)
        assert sent and sent[0] < arr.nbytes // 10  # wire << logical
        # Byte counters count wire bytes on both ends, so the conformance
        # invariant survives compression.
        assert (
            client.counter.snapshot()["sent_bytes"]
            == server.counter.snapshot()["recv_bytes"]
        )
        row = ledger.snapshot()[LINK_TCP]
        assert row["logical_bytes"] > row["wire_bytes"]
        assert row["compressed_bytes"] > 0
        assert row["ratio"] > 1.0  # ratio = logical / wire
    finally:
        for comm in (client, server):
            try:
                comm.close()
            except Exception:
                pass
        listener.stop()


def test_concurrent_senders(comm_pair):
    client, server = comm_pair
    n_threads, per_thread = 4, 25

    def sender(t):
        for i in range(per_thread):
            client.send(("m", {"t": t, "i": i}))

    threads = [threading.Thread(target=sender, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    got = [server.recv(timeout=10)[1] for _ in range(n_threads * per_thread)]
    for t in threads:
        t.join()
    # Every message arrives intact, and per-thread order is preserved.
    for t in range(n_threads):
        seqs = [m["i"] for m in got if m["t"] == t]
        assert seqs == list(range(per_thread))


def test_close_wakes_blocked_peer(comm_pair):
    client, server = comm_pair
    errs = []

    def blocked_recv():
        try:
            server.recv(timeout=30)
        except ChannelClosed:
            errs.append("closed")

    t = threading.Thread(target=blocked_recv)
    t.start()
    time.sleep(0.2)  # let it block
    client.close()
    t.join(timeout=5)
    assert not t.is_alive(), "peer recv hung after close"
    assert errs == ["closed"]


def test_close_wakes_own_blocked_recv(comm_pair):
    client, server = comm_pair
    errs = []

    def blocked_recv():
        try:
            client.recv(timeout=30)
        except ChannelClosed:
            errs.append("closed")

    t = threading.Thread(target=blocked_recv)
    t.start()
    time.sleep(0.2)
    client.close()
    t.join(timeout=5)
    assert not t.is_alive(), "own recv hung after close"
    assert errs == ["closed"]


def test_queued_messages_deliver_before_close(comm_pair):
    client, server = comm_pair
    client.send(("last", {"x": 1}))
    client.close()
    tag, p = server.recv(timeout=5)
    assert (tag, p["x"]) == ("last", 1)
    with pytest.raises(ChannelClosed):
        server.recv(timeout=5)


def test_send_after_close_raises(comm_pair):
    client, server = comm_pair
    client.close()
    with pytest.raises(ChannelClosed):
        client.send(("x", {}))


def test_recv_timeout(comm_pair):
    client, _ = comm_pair
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        client.recv(timeout=0.3)
    assert time.monotonic() - t0 < 5


def test_connect_refused():
    with pytest.raises(ConnectionRefusedError):
        C.connect("inproc://nobody-home")
    with pytest.raises(ValueError):
        C.connect("bogus://x")
    with pytest.raises(ValueError):
        C.connect("no-scheme-at-all")


# ---------------------------------------------------------------------------
# legacy channel shapes keep the new close semantics


def test_local_channel_close_wakes_blocked_peer():
    ch = LocalChannel("hang-fix")
    a, b = ch.endpoint_a(), ch.endpoint_b()
    done = []

    def blocked():
        try:
            b.recv(timeout=30)
        except ChannelClosed:
            done.append(True)

    t = threading.Thread(target=blocked)
    t.start()
    time.sleep(0.2)
    a.close()
    t.join(timeout=5)
    assert done == [True], "LocalChannel peer recv hung after close"


def test_pipe_endpoint_close_wakes_blocked_recv():
    import multiprocessing as mp

    c1, c2 = mp.Pipe()
    a, b = PipeEndpoint(c1, "a"), PipeEndpoint(c2, "b")
    done = []

    def blocked():
        try:
            b.recv(timeout=30)
        except ChannelClosed:
            done.append(True)

    t = threading.Thread(target=blocked)
    t.start()
    time.sleep(0.2)
    a.close()
    t.join(timeout=5)
    assert done == [True], "PipeEndpoint peer recv hung after close"
    with pytest.raises(ChannelClosed):
        a.send(("x", {}))


# ---------------------------------------------------------------------------
# live process-worker clusters
#
# Task functions must be module-level: spawned interpreters import them
# by reference (and this module stays jax-free, so children start fast).


def _double(x):
    return x * 2


def _fail(x):
    raise ValueError(f"boom-{x}")


def _big_result(n):
    return np.arange(n, dtype=np.float64)


def _slow_echo(x, delay=0.3):
    time.sleep(delay)
    return x


def _jax_platforms():
    import os

    return os.environ.get("JAX_PLATFORMS")


def _process_cluster(n_workers=2, **kw):
    from repro.api import ClusterSpec

    kw.setdefault("heartbeat_timeout", 10.0)
    return ClusterSpec(
        n_workers, worker_kind="process", transport="tcp", **kw
    ).build()


@pytest.mark.slow
def test_process_cluster_submit_gather():
    with _process_cluster(2) as cluster:
        cluster.wait_for_workers(timeout=90)
        client = cluster.get_client()
        futs = [client.submit(_double, i) for i in range(32)]
        assert sorted(f.result(timeout=120) for f in futs) == [
            2 * i for i in range(32)
        ]
        # Children really are separate interpreters.
        import os

        pids = {w.pid for w in cluster.workers.values()}
        assert os.getpid() not in pids and len(pids) == 2


@pytest.mark.slow
def test_process_workers_stay_off_the_accelerator(monkeypatch):
    """Spawned workers run jax on the CPU whatever the parent's platform:
    the parent may hold the one chip.  The parent's environment is left
    as it was."""
    import os

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with _process_cluster(1) as cluster:
        cluster.wait_for_workers(timeout=90)
        client = cluster.get_client()
        assert client.submit(_jax_platforms).result(timeout=120) == "cpu"
    assert os.environ["JAX_PLATFORMS"] == "tpu"


@pytest.mark.slow
def test_process_cluster_error_propagation():
    with _process_cluster(1) as cluster:
        cluster.wait_for_workers(timeout=90)
        client = cluster.get_client()
        fut = client.submit(_fail, 7)
        with pytest.raises(RuntimeError, match="boom-7"):
            fut.result(timeout=120)
        # The cluster survives a task failure.
        assert client.submit(_double, 4).result(timeout=120) == 8


@pytest.mark.slow
def test_process_cluster_large_result_via_store_tier():
    with _process_cluster(2) as cluster:
        cluster.wait_for_workers(timeout=90)
        client = cluster.get_client()
        out = client.submit(_big_result, 300_000).result(timeout=120)
        np.testing.assert_array_equal(out, np.arange(300_000, dtype=np.float64))
        # 2.4MB >> inline_result_max: the bytes moved through the shared
        # file-store tier, not through the scheduler.
        assert out.nbytes > cluster.scheduler.inline_result_max


@pytest.mark.slow
def test_process_worker_crash_recovers_lineage():
    with _process_cluster(2, heartbeat_timeout=2.0) as cluster:
        cluster.wait_for_workers(timeout=90)
        client = cluster.get_client()
        futs = [client.submit(_slow_echo, i, pure=False) for i in range(10)]
        futs[0].result(timeout=120)  # work has started
        victim = next(iter(cluster.workers))
        cluster.kill_worker(victim)
        # Tasks stranded on the dead worker reschedule after the
        # heartbeat timeout reaps it.
        assert sorted(f.result(timeout=120) for f in futs) == list(range(10))


@pytest.mark.slow
def test_worker_stats_survive_the_wire():
    with _process_cluster(2) as cluster:
        cluster.wait_for_workers(timeout=90)
        client = cluster.get_client()
        futs = [client.submit(_big_result, 100_000, pure=False) for i in range(4)]
        [f.result(timeout=120) for f in futs]
        deadline = time.monotonic() + 15
        rows = {}
        while time.monotonic() < deadline:
            rows = {
                k: v for k, v in cluster.worker_stats().items() if "state" in v
            }
            if len(rows) == 2:
                break
            time.sleep(0.2)
        assert len(rows) == 2, f"heartbeat stats never arrived: {rows}"
        for wid, row in rows.items():
            assert row["state"] in ("running", "paused")
            for field in (
                "managed_bytes",
                "spilled_bytes",
                "bytes_moved",
                "bytes_copied",
                "copies_per_byte",
                "zero_copy_hits",
                "transfer_ledger",
            ):
                assert field in row, f"{wid} missing {field}"
            assert isinstance(row["transfer_ledger"], dict)
            ws = cluster.scheduler.workers[wid]
            assert ws.last_stats is not None
            assert ws.last_stats["managed_bytes"] == row["managed_bytes"]
