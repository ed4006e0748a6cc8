"""TcpRing unit tier (serving/transport.py — ROADMAP item 1, the
multi-host data plane under docs/SERVING_CLUSTER.md).

Pins the ShmRing producer/consumer contract onto the socket ring:
whole-frame framing round-trips (both directions, empty through large),
torn-frame / partial-read tolerance (a frame dribbled across many TCP
segments assembles invisibly), backpressure-vs-peer-death discipline (a
full ring and a silent wire raise TimeoutError; only a GRACEFUL close
raises BrokenPipeError — connection loss is silence, never a death
verdict), dial-before-listen attach retries, reconnect-after-drop with
at-least-once delivery of the in-flight frame, and endpoint discovery
over the real native TCPStore (the exact path EngineCluster workers
take).  Threads and sockets only — no fork, no engine — so this module
rides the shared tier-1 shard."""

import socket
import struct
import threading
import time

import pytest

from paddle_tpu.serving.transport import (ShmTransport, TcpRing,
                                          TcpTransport, get_transport,
                                          reset_transport_stats,
                                          transport_stats)

_HDR = struct.Struct(">Q")


def _pair(capacity=1 << 20, **attach_kw):
    a = TcpRing("t", capacity, create=True)
    b = TcpRing("t", capacity, create=False,
                endpoint=("127.0.0.1", a.port),
                attach_timeout_ms=5000, **attach_kw)
    return a, b


@pytest.fixture(autouse=True)
def _fresh_stats():
    reset_transport_stats()
    yield
    reset_transport_stats()


def test_framing_round_trip_both_directions():
    a, b = _pair()
    try:
        payloads = [b"", b"x", b"hello ring", bytes(range(256)) * 400]
        for p in payloads:
            a.push(p, timeout_ms=5000)
        for p in payloads:
            assert b.pop(timeout_ms=5000) == p  # FIFO, byte-exact
        b.push(b"reply", timeout_ms=5000)
        assert a.pop(timeout_ms=5000) == b"reply"
        st = transport_stats()
        assert st["frames_sent"] == len(payloads) + 1
        assert st["frames_recv"] == len(payloads) + 1
        assert st["tcp_bytes"] > sum(len(p) for p in payloads)
        assert st["reconnects"] == 0
    finally:
        a.destroy()
        b.destroy()


def test_oversize_item_raises_value_error():
    a = TcpRing("big", capacity=128, create=True)
    try:
        with pytest.raises(ValueError):
            a.push(b"z" * 128)  # frame = header + payload > capacity
    finally:
        a.destroy()


def test_pop_deadline_raises_timeout():
    a, b = _pair()
    try:
        with pytest.raises(TimeoutError):
            b.pop(timeout_ms=50)
    finally:
        a.destroy()
        b.destroy()


def test_graceful_close_drains_then_none_then_broken_pipe():
    a, b = _pair()
    try:
        a.push(b"one", timeout_ms=5000)
        a.push(b"two", timeout_ms=5000)
        a.close()  # CLOSE sentinel queues BEHIND the data frames
        assert b.pop(timeout_ms=5000) == b"one"
        assert b.pop(timeout_ms=5000) == b"two"
        deadline = time.monotonic() + 5
        while True:  # drained + sentinel seen -> None, not TimeoutError
            try:
                assert b.pop(timeout_ms=200) is None
                break
            except TimeoutError:
                assert time.monotonic() < deadline, "CLOSE never arrived"
        with pytest.raises(BrokenPipeError):
            a.push(b"after local close")
        with pytest.raises(BrokenPipeError):
            b.push(b"after peer close")
    finally:
        a.destroy()
        b.destroy()


def test_backpressure_full_ring_times_out_never_death():
    # no peer ever connects: frames park in the bounded send queue and a
    # full ring is BACKPRESSURE (TimeoutError), not a death verdict
    a = TcpRing("bp", capacity=64, create=True)
    try:
        a.push(b"x" * 40, timeout_ms=200)  # 48B frame fits
        with pytest.raises(TimeoutError):
            a.push(b"y" * 40, timeout_ms=200)  # second would exceed 64
    finally:
        a.destroy()


def test_abrupt_peer_disconnect_is_silence_not_death():
    # a raw peer connects then vanishes WITHOUT the CLOSE sentinel (the
    # SIGKILL shape): push keeps queueing, pop times out — only the
    # failure detector may pronounce death
    a = TcpRing("silent", capacity=1 << 16, create=True)
    raw = socket.create_connection(("127.0.0.1", a.port), timeout=5)
    try:
        a.push(b"queued before drop", timeout_ms=5000)
        raw.close()  # FIN, no sentinel
        time.sleep(0.1)
        a.push(b"queued after drop", timeout_ms=5000)  # no BrokenPipeError
        with pytest.raises(TimeoutError):
            a.pop(timeout_ms=100)
    finally:
        a.destroy()


def test_torn_frames_assemble_across_segments():
    a = TcpRing("torn", capacity=1 << 16, create=True)
    raw = socket.create_connection(("127.0.0.1", a.port), timeout=5)
    try:
        payload = b"torn-frame-payload"
        frame = _HDR.pack(len(payload)) + payload
        # dribble: split inside the header, then inside the payload
        for chunk in (frame[:3], frame[3:10], frame[10:]):
            raw.sendall(chunk)
            time.sleep(0.05)
        assert a.pop(timeout_ms=5000) == payload
        # two whole frames in ONE segment -> two pops
        two = (_HDR.pack(2) + b"ab") + (_HDR.pack(3) + b"cde")
        raw.sendall(two)
        assert a.pop(timeout_ms=5000) == b"ab"
        assert a.pop(timeout_ms=5000) == b"cde"
    finally:
        raw.close()
        a.destroy()


def test_dial_before_listen_attach_retries():
    # reserve a port, then attach BEFORE the listener exists — the
    # ShmRing startup race the fresh-socket retry loop absorbs
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    box = {}

    def _attach():
        box["ring"] = TcpRing("late", create=False,
                              endpoint=("127.0.0.1", port),
                              attach_timeout_ms=8000)

    t = threading.Thread(target=_attach)
    t.start()
    time.sleep(0.3)  # the dialer is already retrying against nothing
    a = TcpRing("late", create=True, port=port)
    t.join(timeout=10)
    b = box.get("ring")
    assert b is not None, "attach never connected"
    try:
        a.push(b"made it", timeout_ms=5000)
        assert b.pop(timeout_ms=5000) == b"made it"
    finally:
        a.destroy()
        b.destroy()


def test_frame_pushed_before_the_drop_is_noticed_is_not_lost():
    """The peer's FIN is in the kernel but the rx thread has not acted on
    it yet (made late here; under load it is late by itself): a frame
    pushed now must wait for the replacement connection, not vanish into
    the dead one."""
    a, b = _pair()
    try:
        a.push(b"before", timeout_ms=5000)
        assert b.pop(timeout_ms=5000) == b"before"
        drop = b._drop

        def late_drop(gen):
            time.sleep(0.5)
            drop(gen)

        b._drop = late_drop
        with a._cv:
            conn = a._conn
        conn.shutdown(socket.SHUT_RDWR)
        conn.close()
        b.push(b"uphill", timeout_ms=5000)
        assert a.pop(timeout_ms=20000) == b"uphill"
    finally:
        a.destroy()
        b.destroy()


def test_dial_without_listener_fails_at_deadline():
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    with pytest.raises(ConnectionError):
        TcpRing("nobody", create=False, endpoint=("127.0.0.1", port),
                attach_timeout_ms=200)


def test_attach_requires_endpoint():
    with pytest.raises(ValueError):
        TcpRing("lost", create=False)


def _pop_until(ring, expected, *, absorb=(), deadline_s=20.0):
    """Pop until `expected` arrives.  At-least-once across a drop means an
    already-delivered frame may be re-sent whole (the sender can lose the
    connection between sendall returning and the in-flight frame leaving
    its queue), so duplicates of frames in `absorb` are skipped — anything
    else is a real ordering violation."""
    end = time.monotonic() + deadline_s
    while True:
        got = ring.pop(
            timeout_ms=int(max(1, (end - time.monotonic()) * 1000)))
        if got == expected:
            return
        assert got in absorb, got


def test_reconnect_after_drop_resumes_and_redelivers():
    a, b = _pair()
    try:
        a.push(b"before", timeout_ms=5000)
        assert b.pop(timeout_ms=5000) == b"before"
        # hard-drop the live connection out from under both ends: the
        # create side must re-accept, the attach side must redial
        with a._cv:
            conn = a._conn
        conn.shutdown(socket.SHUT_RDWR)
        conn.close()
        # frames pushed across the drop boundary arrive AT LEAST once on
        # the replacement connection — silence, then resumption; a
        # duplicate of the already-delivered frame is legal redelivery
        a.push(b"across the drop", timeout_ms=5000)
        b.push(b"uphill too", timeout_ms=5000)
        _pop_until(b, b"across the drop", absorb={b"before"})
        _pop_until(a, b"uphill too")
        assert transport_stats()["reconnects"] >= 1
    finally:
        a.destroy()
        b.destroy()


def test_tcp_transport_discovers_endpoint_via_store():
    # the exact worker path: the router publishes ep:<ring> on the
    # TCPStore control tier, the (possibly remote) worker waits on the
    # key and dials under the same attach deadline
    from paddle_tpu import _native

    srv = _native.TCPStoreServer()
    store = _native.TCPStoreClient(port=srv.port)
    tr = get_transport("tcp", store)
    assert isinstance(tr, TcpTransport)
    ring = tr.create("in:w0", 1 << 16)
    try:
        worker_store = _native.TCPStoreClient(port=srv.port)
        peer = get_transport("tcp", worker_store).attach("in:w0", 5000)
        try:
            peer.push(b"hello router", timeout_ms=5000)
            assert ring.pop(timeout_ms=5000) == b"hello router"
        finally:
            peer.destroy()
    finally:
        ring.destroy()


def test_tcp_transport_attach_times_out_without_publication():
    from paddle_tpu import _native

    srv = _native.TCPStoreServer()
    store = _native.TCPStoreClient(port=srv.port)
    with pytest.raises(Exception):  # store.get deadline: key never set
        TcpTransport(store).attach("never-published", 300)


def test_get_transport_resolution_and_flag_default():
    assert isinstance(get_transport("shm"), ShmTransport)
    # "" resolves FLAGS_cluster_transport, whose baked default is shm
    assert isinstance(get_transport(""), ShmTransport)
    with pytest.raises(ValueError):
        get_transport("carrier-pigeon")
    with pytest.raises(ValueError):
        TcpTransport(None)  # tcp NEEDS the store for discovery


def test_stats_reset_zeroes_counters():
    a, b = _pair()
    try:
        a.push(b"tick", timeout_ms=5000)
        assert b.pop(timeout_ms=5000) == b"tick"
    finally:
        a.destroy()
        b.destroy()
    assert transport_stats()["frames_sent"] >= 1
    out = transport_stats(reset=True)
    assert out["frames_sent"] >= 1  # the pre-reset snapshot is returned
    assert transport_stats() == {"tcp_bytes": 0, "reconnects": 0,
                                 "frames_sent": 0, "frames_recv": 0}


def test_large_frame_outlives_send_timeout_no_reconnect():
    """REVIEW regression: the socket's 0.2s timeout bounds the TOTAL
    duration of ``sendall``, so a frame bigger than the kernel send
    buffer used to time out mid-send, get treated as a connection drop,
    and livelock (reconnect -> re-send whole -> time out again) while
    the receiver stalled.  The chunked send must ride out a stalled
    reader as BACKPRESSURE — progress resets the clock, zero drops."""
    ring = TcpRing("chunked", capacity=8 << 20, create=True)
    s_tx, s_rx = socket.socketpair()
    try:
        # a small kernel buffer + a reader parked well past the 0.2s
        # socket timeout forces multiple per-chunk timeouts
        s_tx.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 14)
        s_tx.settimeout(0.2)
        frame = bytes(range(256)) * 8192  # 2 MiB >> SNDBUF
        out = {}

        def _send():
            out["ok"] = ring._send_frame(s_tx, ring._conn_gen, frame)

        t = threading.Thread(target=_send, daemon=True)
        t.start()
        time.sleep(0.6)  # >= 2 chunk timeouts while nobody reads
        got = bytearray()
        s_rx.settimeout(10)
        while len(got) < len(frame):
            data = s_rx.recv(1 << 16)
            assert data, "sender gave up mid-frame"
            got += data
        t.join(timeout=10)
        assert not t.is_alive(), "send never completed"
        assert out["ok"] is True
        assert bytes(got) == frame  # intact, exactly once
        assert transport_stats()["reconnects"] == 0
    finally:
        s_tx.close()
        s_rx.close()
        ring.destroy()


def test_receiver_backpressure_bounds_memory_and_stalls_push():
    """REVIEW regression: the rx thread used to drain the socket into
    an UNBOUNDED queue regardless of pop() rate, so a stalled consumer
    let the producer run arbitrarily far ahead — ShmRing's capacity
    contract did not hold end-to-end.  With recv paused past capacity,
    TCP flow control must back the pipe up until push() itself times
    out, with receiver-side buffering bounded near capacity."""
    cap = 1 << 16
    a, b = _pair(capacity=cap)
    try:
        seq_size = 1 << 15  # 32 KiB payloads, each well under capacity
        pushed = 0
        stalled = False
        # 32 MiB ceiling: far beyond capacity + any autotuned kernel
        # socket buffering, so an unbounded receiver would swallow it
        # all without ever stalling the producer
        for i in range(1024):
            payload = _HDR.pack(i) + b"p" * (seq_size - _HDR.size)
            try:
                a.push(payload, timeout_ms=400)
            except TimeoutError:
                stalled = True
                break
            pushed += 1
        assert stalled, "push never felt the stalled consumer"
        with b._cv:
            buffered = b._recv_bytes + len(b._rbuf)
        assert buffered <= cap + (1 << 16), buffered  # one recv of slack
        # nothing was lost or duplicated under the stall: every accepted
        # frame arrives, in order, once the consumer drains
        for i in range(pushed):
            got = b.pop(timeout_ms=10_000)
            assert got is not None and _HDR.unpack_from(got)[0] == i
        with pytest.raises(TimeoutError):
            b.pop(timeout_ms=100)
        assert transport_stats()["reconnects"] == 0
    finally:
        a.destroy()
        b.destroy()
