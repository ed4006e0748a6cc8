"""Socket data plane for the serving cluster (ROADMAP item 1,
docs/SERVING_CLUSTER.md "Multi-host data plane").

`TcpRing` is a length-framed byte channel over one TCP connection with
the EXACT producer/consumer contract of `_native.ShmRing`:

- ``push(data, timeout_ms)``   whole-frame-or-nothing enqueue into a
  capacity-bounded send queue.  A full queue past the deadline raises
  ``TimeoutError`` — BACKPRESSURE, never a death verdict.  An oversize
  item raises ``ValueError``.  A ring the peer has gracefully closed
  raises ``BrokenPipeError``.
- ``pop(timeout_ms)``          next whole frame, ``None`` once the peer
  closed and the queue drained, ``TimeoutError`` at the deadline.
  Partial frames persist across pops (torn-frame tolerance): a frame
  split over many TCP segments assembles invisibly.  The capacity bound
  holds END-TO-END: the rx thread stops draining the socket past
  ``capacity`` buffered-unpopped bytes, so TCP flow control backs the
  pipe up until the remote push genuinely blocks — a stalled consumer
  bounds its producer exactly like shm, not just the send window.
- ``close()`` / ``destroy()``  graceful close (a CLOSE sentinel frame
  rides the wire so the peer's pop drains to ``None``) / teardown.

The ONE semantic divergence from shm — and it is deliberate — is death
detection.  ShmRing poisons on a peer dying mid-operation; TCP cannot
distinguish a SIGKILLed peer's FIN/RST from a transient network drop, so
`TcpRing` treats connection loss as SILENCE, not death: the attach side
redials with backoff (``reconnects`` counts the successes), the create
side keeps listening for a replacement connection, unsent whole frames
are retained and re-sent, and a frame in flight across a drop is
delivered at-least-once (the wire protocol is re-emission-safe by
design: nonce identity + the router's per-position merge).  Meanwhile
push sees backpressure and pop sees timeouts — the failure detector
(heartbeats + child exit) remains the only death authority, exactly the
`backpressure-not-death` invariant the protocol model checker proves
over the tcp semantics (static/protocol_lint.py, the `clean-tcp-ring`
scenario with its reconnect-after-drop transition).

Endpoint discovery rides the existing TCPStore control tier: the
creating (router) side publishes ``ep:<ring_name>`` -> ``host:port`` and
the attaching (worker) side blocks on the key under the shared attach
deadline (`FLAGS_cluster_attach_timeout_ms`), then dials on fresh
sockets until the same deadline — a consumer routinely outraces the
producer's bind, the same startup race the ShmRing attach retry absorbs.

`RingTransport` (ShmTransport | TcpTransport) is the construction knob:
`EngineCluster(transport="shm"|"tcp")` / `FLAGS_cluster_transport` pick
one, and cluster.py / cluster_worker.py stay transport-agnostic.
"""

from __future__ import annotations

import collections
import random
import select
import socket
import struct
import threading
import time

__all__ = ["TcpRing", "ShmTransport", "TcpTransport", "get_transport",
           "transport_stats", "reset_transport_stats"]

# ---------------------------------------------------------------- telemetry
# Wire-level counters (cluster_stats() folds them in — the module that
# owns the socket owns the counters): tcp_bytes counts every framed byte
# handed to the kernel, frames_sent/frames_recv count whole data frames
# (the CLOSE sentinel is excluded), reconnects counts connections
# re-established AFTER a drop (first connects are not reconnects).
_TRANSPORT_STATS = {
    "tcp_bytes": 0,
    "reconnects": 0,
    "frames_sent": 0,
    "frames_recv": 0,
}
_stats_mu = threading.Lock()


def transport_stats(reset: bool = False) -> dict:
    """Socket-transport counters (docs/SERVING_CLUSTER.md multi-host
    section).  All-zero when every ring in this process is shm."""
    with _stats_mu:
        out = dict(_TRANSPORT_STATS)
        if reset:
            for k in _TRANSPORT_STATS:
                _TRANSPORT_STATS[k] = 0
    return out


def reset_transport_stats():
    transport_stats(reset=True)


def _bump(key, n=1):
    with _stats_mu:
        _TRANSPORT_STATS[key] += n


# A CLOSE sentinel frame: a length no real frame can carry.  It rides
# the ordinary frame stream so it cannot overtake queued data.
_HDR = struct.Struct(">Q")
_CLOSE_LEN = (1 << 64) - 1
_CLOSE_FRAME = _HDR.pack(_CLOSE_LEN)


class TcpRing:
    """One length-framed byte channel over TCP; ShmRing's contract.

    ``create=True`` binds a listener (ephemeral port unless ``port`` is
    given) and accepts — including REPLACEMENT connections after a drop.
    ``create=False`` dials ``endpoint`` with fresh-socket retries until
    ``attach_timeout_ms`` (dial-before-listen tolerance), then redials in
    the background whenever the connection drops.
    """

    def __init__(self, name: str, capacity: int = 64 << 20, create=True,
                 endpoint=None, attach_timeout_ms: int = 0,
                 host="127.0.0.1", port=0):
        self.name = name
        self.capacity = int(capacity)
        self._create = bool(create)
        self._cv = threading.Condition()
        self._sendq = collections.deque()   # framed bytes, head = in flight
        self._send_bytes = 0
        self._recvq = collections.deque()   # whole payloads, ready to pop
        self._recv_bytes = 0                # payload bytes parked in _recvq
        self._rbuf = bytearray()            # partial frame across segments
        self._conn = None
        self._conn_gen = 0
        self._ever_connected = False
        self._closed_local = False
        self._peer_closed = False
        self._destroyed = False
        self._lsock = None
        if create:
            self._lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            self._lsock.setsockopt(socket.SOL_SOCKET,
                                   socket.SO_REUSEADDR, 1)
            self._lsock.bind((host, int(port)))
            self._lsock.listen(4)
            self._lsock.settimeout(0.1)
            self.host, self.port = self._lsock.getsockname()[:2]
        else:
            if endpoint is None:
                raise ValueError("TcpRing attach needs endpoint=(host, "
                                 "port) — publish it via the TCPStore "
                                 "(TcpTransport) or pass it explicitly")
            self.host, self.port = str(endpoint[0]), int(endpoint[1])
            self._set_conn(self._dial_until(attach_timeout_ms))
        self._rx = threading.Thread(target=self._rx_loop, daemon=True,
                                    name=f"tcpring-rx:{name}")
        self._tx = threading.Thread(target=self._tx_loop, daemon=True,
                                    name=f"tcpring-tx:{name}")
        self._rx.start()
        self._tx.start()

    # --------------------------------------------------------- connection
    def _dial_once(self, timeout_s=0.25):
        s = socket.create_connection((self.host, self.port),
                                     timeout=timeout_s)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.settimeout(0.2)
        return s

    def _dial_until(self, attach_timeout_ms):
        """Fresh-socket dial retries under ONE deadline — first-refusal
        failure is the wrong contract for a constructor racing the
        listener's bind (the ShmRing attach lesson).  0 keeps the
        fail-on-first-refusal behaviour."""
        deadline = time.monotonic() + max(attach_timeout_ms, 0) / 1000.0
        delay = 0.005
        while True:
            try:
                return self._dial_once()
            except OSError:
                if time.monotonic() >= deadline:
                    raise ConnectionError(
                        f"tcp ring dial failed: {self.name} at "
                        f"{self.host}:{self.port} (no listener within "
                        f"{attach_timeout_ms}ms)") from None
            time.sleep(random.uniform(0, min(delay, 0.1)))
            delay *= 2

    def _set_conn(self, conn):
        with self._cv:
            if self._destroyed:
                try:
                    conn.close()
                except OSError:
                    pass
                return
            self._conn = conn
            self._conn_gen += 1
            if self._ever_connected:
                _bump("reconnects")
            self._ever_connected = True
            self._cv.notify_all()

    def _drop(self, gen):
        """Connection loss is SILENCE: discard the torn partial frame
        (the sender re-sends its in-flight frame whole), keep queued
        frames, and let the rx loop accept/redial a replacement."""
        with self._cv:
            if self._conn is None or self._conn_gen != gen:
                return
            try:
                self._conn.close()
            except OSError:
                pass
            self._conn = None
            del self._rbuf[:]
            self._cv.notify_all()

    # ----------------------------------------------------------- io loops
    def _rx_loop(self):
        while True:
            with self._cv:
                if self._destroyed:
                    return
                conn, gen = self._conn, self._conn_gen
                # Strict >: _rbuf holds at most ONE partial frame (parse
                # runs on every recv), so a max-size frame with an empty
                # recvq reaches exactly `capacity` buffered and must
                # still complete — `>=` would park it forever.
                if (conn is not None and self._recv_bytes
                        + len(self._rbuf) > self.capacity):
                    # Receiver-side backpressure: a consumer that stops
                    # popping must stall the remote producer, or the
                    # capacity contract only bounds the SEND window and
                    # this queue grows without limit.  Stop draining the
                    # socket; TCP flow control fills the sender's kernel
                    # buffer until its push() genuinely blocks.
                    self._cv.wait(0.2)
                    continue
            if conn is None:
                self._reconnect_step()
                continue
            try:
                data = conn.recv(1 << 16)
            except socket.timeout:
                continue
            except OSError:
                self._drop(gen)
                continue
            if not data:  # FIN: silence, not a death verdict
                self._drop(gen)
                continue
            with self._cv:
                if self._conn_gen != gen:
                    continue  # raced a drop: bytes belong to a dead conn
                self._rbuf += data
                self._parse_frames()
                self._cv.notify_all()

    def _reconnect_step(self):
        """One accept (create side) or redial (attach side) attempt."""
        if self._create:
            try:
                conn, _addr = self._lsock.accept()
            except (socket.timeout, OSError):
                return
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            conn.settimeout(0.2)
            self._set_conn(conn)
            return
        try:
            conn = self._dial_once()
        except OSError:
            time.sleep(random.uniform(0.005, 0.05))
            return
        self._set_conn(conn)

    def _parse_frames(self):
        # caller holds self._cv
        while True:
            if len(self._rbuf) < _HDR.size:
                return
            (n,) = _HDR.unpack_from(self._rbuf)
            if n == _CLOSE_LEN:
                del self._rbuf[:_HDR.size]
                self._peer_closed = True
                continue
            if len(self._rbuf) < _HDR.size + n:
                return  # torn frame: keep the partial for the next recv
            payload = bytes(self._rbuf[_HDR.size:_HDR.size + n])
            del self._rbuf[:_HDR.size + n]
            self._recvq.append(payload)
            self._recv_bytes += len(payload)

    def _tx_loop(self):
        while True:
            with self._cv:
                while (not self._destroyed
                       and (self._conn is None or not self._sendq)):
                    self._cv.wait(0.2)
                if self._destroyed:
                    return
                conn, gen = self._conn, self._conn_gen
                frame = self._sendq[0]
            if not self._send_frame(conn, gen, frame):
                continue
            with self._cv:
                if (self._conn_gen != gen or not self._sendq
                        or self._sendq[0] is not frame):
                    # dropped mid-ack: the frame stays queued and will be
                    # re-sent whole on the replacement connection
                    # (at-least-once across a drop boundary)
                    continue
                self._sendq.popleft()
                self._send_bytes -= len(frame)
                self._cv.notify_all()
            _bump("tcp_bytes", len(frame))
            if frame is not _CLOSE_FRAME:
                _bump("frames_sent")

    _SEND_CHUNK = 1 << 16

    @staticmethod
    def _peer_hung_up(conn):
        """True when the kernel already holds the peer's FIN or RST for
        `conn`.  A send into such a connection succeeds and the bytes are
        lost, so the tx loop asks before every frame and does not wait
        for the rx thread to get round to the same news (under load it
        came late, and a frame pushed across a drop never arrived)."""
        try:
            readable, _, _ = select.select([conn], [], [], 0)
            return bool(readable) and conn.recv(1, socket.MSG_PEEK) == b""
        except socket.timeout:
            return False  # the rx thread took the bytes first: alive
        except (OSError, ValueError):
            return True

    def _send_frame(self, conn, gen, frame):
        """Write one frame in bounded chunks.  The socket's 0.2s timeout
        bounds the TOTAL duration of ``sendall`` (not per-syscall), so a
        frame larger than the kernel send buffer — routine for multi-MB
        ship_block K/V payloads on a real cross-host link — would time
        out mid-send forever if sent whole: timeout -> treated as drop
        -> reconnect -> re-send the SAME frame -> timeout again, a
        livelock loopback tests cannot reproduce.  Chunking makes the
        timeout per-chunk, so any progress resets the clock; a chunk
        timeout means the kernel buffer is full (peer not draining) and
        is BACKPRESSURE — retry on the same connection — while only a
        real socket error is a drop.  Returns True when the frame went
        out whole on this connection."""
        if self._peer_hung_up(conn):
            self._drop(gen)
            return False
        view = memoryview(frame)
        off = 0
        while off < len(view):
            with self._cv:
                if self._destroyed or self._conn_gen != gen:
                    # dropped (or torn down) mid-frame: the peer discards
                    # its torn partial; the frame stays at the sendq head
                    # and is re-sent whole on the replacement connection
                    return False
            try:
                off += conn.send(view[off:off + self._SEND_CHUNK])
            except socket.timeout:
                continue  # kernel buffer full: backpressure, not death
            except OSError:
                self._drop(gen)
                return False
        return True

    # ------------------------------------------------------ ring contract
    def push(self, data: bytes, timeout_ms=-1):
        nb = len(data)
        if nb + _HDR.size > self.capacity:
            raise ValueError("item larger than ring capacity")
        frame = _HDR.pack(nb) + bytes(data)
        deadline = (None if timeout_ms is None or timeout_ms < 0
                    else time.monotonic() + timeout_ms / 1000.0)
        with self._cv:
            while True:
                if self._destroyed or self._closed_local:
                    raise BrokenPipeError("ring closed")
                if self._peer_closed:
                    raise BrokenPipeError("ring closed (peer closed)")
                if self._send_bytes + len(frame) <= self.capacity:
                    self._sendq.append(frame)
                    self._send_bytes += len(frame)
                    self._cv.notify_all()
                    return
                rem = (None if deadline is None
                       else deadline - time.monotonic())
                if rem is not None and rem <= 0:
                    raise TimeoutError("ring push timed out")
                self._cv.wait(0.2 if rem is None else min(rem, 0.2))

    def pop(self, timeout_ms=-1):
        deadline = (None if timeout_ms is None or timeout_ms < 0
                    else time.monotonic() + timeout_ms / 1000.0)
        with self._cv:
            while True:
                if self._recvq:
                    payload = self._recvq.popleft()
                    self._recv_bytes -= len(payload)
                    self._cv.notify_all()
                    _bump("frames_recv")
                    return payload
                if (self._peer_closed or self._closed_local
                        or self._destroyed):
                    return None  # closed and drained
                rem = (None if deadline is None
                       else deadline - time.monotonic())
                if rem is not None and rem <= 0:
                    raise TimeoutError("ring pop timed out")
                self._cv.wait(0.2 if rem is None else min(rem, 0.2))

    def close(self):
        """Graceful close: queue the CLOSE sentinel BEHIND any pending
        frames so the peer drains everything, then sees None."""
        with self._cv:
            if self._closed_local or self._destroyed:
                return
            self._closed_local = True
            self._sendq.append(_CLOSE_FRAME)
            self._send_bytes += len(_CLOSE_FRAME)
            self._cv.notify_all()

    def destroy(self):
        with self._cv:
            if self._destroyed:
                return
            self._destroyed = True
            conn = self._conn
            self._conn = None
            self._cv.notify_all()
        for s in (conn, self._lsock):
            if s is not None:
                try:
                    s.close()
                except OSError:
                    pass
        for t in (self._rx, self._tx):
            if t.is_alive() and t is not threading.current_thread():
                t.join(timeout=2.0)

    def __del__(self):
        try:
            self.destroy()
        except Exception:
            pass


# ================================================================ transports
def _ep_key(ring_name: str) -> str:
    return f"ep:{ring_name}"


class ShmTransport:
    """Today's single-box data plane: `_native.ShmRing`, verbatim."""

    name = "shm"

    def __init__(self, store=None):
        del store  # shm needs no endpoint discovery

    def create(self, ring_name: str, capacity: int):
        from paddle_tpu import _native

        return _native.ShmRing(ring_name, capacity)

    def attach(self, ring_name: str, attach_timeout_ms: int):
        from paddle_tpu import _native

        return _native.ShmRing(ring_name, create=False,
                               attach_timeout_ms=attach_timeout_ms)


class TcpTransport:
    """Multi-host data plane: TcpRing endpoints published through the
    TCPStore control tier (which already spans hosts).  The CREATE side
    (the router) listens and publishes; the ATTACH side (a worker,
    possibly on another host) waits for the endpoint key and dials —
    both halves of the attach share ONE deadline."""

    name = "tcp"

    def __init__(self, store, host="127.0.0.1"):
        if store is None:
            raise ValueError("TcpTransport needs a TCPStore client for "
                             "endpoint discovery")
        self._store = store
        self._host = host

    def create(self, ring_name: str, capacity: int):
        ring = TcpRing(ring_name, capacity, create=True, host=self._host)
        self._store.set(_ep_key(ring_name),
                        f"{ring.host}:{ring.port}".encode())
        return ring

    def attach(self, ring_name: str, attach_timeout_ms: int):
        deadline = time.monotonic() + max(attach_timeout_ms, 1) / 1000.0
        ep = self._store.get(_ep_key(ring_name),
                             timeout_ms=max(attach_timeout_ms, 1))
        host, port = ep.decode().rsplit(":", 1)
        remaining_ms = max(int((deadline - time.monotonic()) * 1000), 1)
        return TcpRing(ring_name, create=False,
                       endpoint=(host, int(port)),
                       attach_timeout_ms=remaining_ms)


def get_transport(kind: str, store=None):
    """Resolve a transport name ("shm" | "tcp"; "" -> the
    FLAGS_cluster_transport default) to a RingTransport instance."""
    if not kind:
        from paddle_tpu._core import flags as _flags

        kind = str(_flags.flag("FLAGS_cluster_transport"))
    if kind == "shm":
        return ShmTransport(store)
    if kind == "tcp":
        return TcpTransport(store)
    raise ValueError(f"unknown cluster transport {kind!r} "
                     "(expected 'shm' or 'tcp')")
