"""netchaos — in-process TCP chaos proxy for the distributed tier.

ISSUE 20: the harness that makes gray-failure handling *pinnable*.  A
:class:`NetChaosProxy` sits between the coordinator's data-plane
sockets and one worker's data listener, forwarding byte streams while
injecting network weather per (worker, direction) from a seeded spec:

  * ``delay``      — fixed extra latency per TKD1 frame (the straggler
                     shape: everything arrives, late),
  * ``throttle``   — bandwidth cap in bytes/s (congested link),
  * ``drop_after`` — forward N bytes then silently swallow the rest of
                     the stream (gray partition: the peer never learns),
  * ``half_open``  — one trigger stalls BOTH directions of the
                     connection (the classic half-open TCP session: the
                     peer waits out its socket timeout),
  * ``dup_frame``  — re-emit whole frames with probability p (exercises
                     the store's per-seq idempotence and the client's
                     reply-desync recovery),
  * ``reorder``    — swap adjacent frames with probability p,
  * ``reset``      — hard RST (SO_LINGER 0) after N bytes mid-stream.

Frame-aware kinds (delay / dup_frame / reorder) parse the ``TKD1``
framing so injections land on message boundaries; byte-level kinds
(throttle / drop_after / half_open / reset) act on raw chunks.  All
randomness flows from the spec's seed, so a sweep failure replays.

The proxy is deliberately ignorant of the protocol's *meaning*: it can
only delay, duplicate, damage, or destroy bytes — exactly what a real
network can do — so every test assertion downstream of it is about the
resilience machinery (hedges, DEGRADED demotion, idempotent stores,
CRC surfacing corruption structurally), never about luck.

Wiring: ``interpose(coord, worker_id, spec)`` rewires the registered
worker's host/port to the proxy and evicts the pooled data connection;
``proxy.set_spec``/``proxy.clear`` swap the weather live (a lifted
delay is how the promotion path gets exercised); control-plane
heartbeats do NOT pass through the proxy — a gray data plane with a
healthy control plane is precisely the failure mode under test.
"""
from __future__ import annotations

import random
import socket
import struct
import threading
import time
from typing import Dict, List, Optional, Tuple

from spark_rapids_tpu.distributed.protocol import MAGIC, hard_close

_HDR = struct.Struct("<4sII")

# injection kinds accepted by make_injection / ChaosSpec
KINDS = ("delay", "throttle", "drop_after", "half_open", "dup_frame",
         "reorder", "reset")
# directions: client(coordinator) -> worker, worker -> client
DIRECTIONS = ("c2w", "w2c")


class _ResetSignal(Exception):
    """Internal: the injection wants a hard RST now."""


def _split_frames(buf: bytes) -> Tuple[List[bytes], bytes]:
    """Split a byte buffer into complete TKD1 frames + the remainder.
    A non-TKD1 prefix (never produced by this protocol, but the proxy
    must not wedge on it) is passed through as one pseudo-frame."""
    frames: List[bytes] = []
    while len(buf) >= _HDR.size:
        magic, plen, _crc = _HDR.unpack_from(buf, 0)
        if magic != MAGIC:
            frames.append(buf)
            return frames, b""
        total = _HDR.size + plen
        if len(buf) < total:
            break
        frames.append(buf[:total])
        buf = buf[total:]
    return frames, buf


class _Injection:
    """One direction's stateful injection.  ``feed(data)`` returns the
    bytes to forward now (possibly sleeping to shape time) or raises
    :class:`_ResetSignal`; ``stalled`` on the shared conn state swallows
    everything once a half-open trigger fired."""

    def __init__(self, kind: str, rng: random.Random, *, delay_s=0.05,
                 bytes_per_s=1 << 20, after_bytes=4096, p=0.25,
                 min_bytes=0):
        if kind not in KINDS:
            raise ValueError(f"unknown injection kind {kind!r}")
        self.kind = kind
        self.rng = rng
        self.delay_s = float(delay_s)
        self.bytes_per_s = max(float(bytes_per_s), 1.0)
        self.after_bytes = int(after_bytes)
        self.p = float(p)
        # delay only frames at least this large: tiny acks pass while
        # data-carrying replies crawl — a congested bulk path under a
        # healthy RPC path, the shape that keeps a straggler's latency
        # estimate honest on small ops while its fetches blow deadlines
        self.min_bytes = int(min_bytes)
        self._buf = b""
        self._seen = 0
        self._held: Optional[bytes] = None   # reorder's parked frame

    def feed(self, data: bytes, state: Dict) -> bytes:
        self._seen += len(data)
        k = self.kind
        if k == "delay":
            frames, self._buf = _split_frames(self._buf + data)
            out = []
            for f in frames:
                if len(f) >= self.min_bytes:
                    time.sleep(self.delay_s)
                out.append(f)
            return b"".join(out)
        if k == "throttle":
            time.sleep(len(data) / self.bytes_per_s)
            return data
        if k == "drop_after":
            if self._seen > self.after_bytes:
                over = self._seen - self.after_bytes
                return data[:max(len(data) - over, 0)]
            return data
        if k == "half_open":
            if self._seen > self.after_bytes:
                state["stalled"] = True
            if state.get("stalled"):
                over = self._seen - self.after_bytes
                return data[:max(len(data) - over, 0)]
            return data
        if k == "dup_frame":
            frames, self._buf = _split_frames(self._buf + data)
            out = []
            for f in frames:
                out.append(f)
                if self.rng.random() < self.p:
                    out.append(f)
            return b"".join(out)
        if k == "reorder":
            frames, self._buf = _split_frames(self._buf + data)
            out = []
            for f in frames:
                if self._held is not None:
                    if self.rng.random() < self.p:
                        out.append(f)
                        out.append(self._held)
                    else:
                        out.append(self._held)
                        out.append(f)
                    self._held = None
                elif self.rng.random() < self.p:
                    self._held = f
                else:
                    out.append(f)
            return b"".join(out)
        if k == "reset":
            if self._seen > self.after_bytes:
                raise _ResetSignal()
            return data
        return data

    def flush(self) -> bytes:
        """End-of-stream: forward anything a frame-aware kind parked."""
        out = self._buf
        self._buf = b""
        if self._held is not None:
            out = self._held + out
            self._held = None
        return out


class ChaosSpec:
    """Seeded per-(worker, direction) injection plan.  ``injections``
    maps a direction (``"c2w"``/``"w2c"``) to ``(kind, params)``; a
    missing direction forwards untouched.  Each accepted connection
    spawns FRESH stateful injections from a connection-local RNG child
    of the seed, so runs replay byte-for-byte."""

    def __init__(self, seed: int,
                 injections: Optional[Dict[str, Tuple[str, Dict]]] = None):
        self.seed = int(seed)
        self.injections = dict(injections or {})
        for d in self.injections:
            if d not in DIRECTIONS:
                raise ValueError(f"unknown direction {d!r}")

    def spawn(self, conn_idx: int) -> Dict[str, Optional[_Injection]]:
        out: Dict[str, Optional[_Injection]] = {}
        for d in DIRECTIONS:
            spec = self.injections.get(d)
            if spec is None:
                out[d] = None
            else:
                kind, params = spec
                rng = random.Random(
                    (self.seed * 1_000_003 + conn_idx * 7919
                     + DIRECTIONS.index(d)) & 0x7FFFFFFF)
                out[d] = _Injection(kind, rng, **params)
        return out


def _rst_close(sock: socket.socket) -> None:
    """Close with RST (SO_LINGER 0) — a mid-stream reset, not FIN."""
    try:
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack("ii", 1, 0))
    except OSError:
        pass
    try:
        sock.close()
    except OSError:
        pass


class NetChaosProxy:
    """One worker's chaos interposer: listens on an ephemeral loopback
    port, forwards every accepted connection to ``(target_host,
    target_port)`` through the current :class:`ChaosSpec`.  The spec is
    swappable live (``set_spec``/``clear``) so a harness can lift the
    weather and watch the DEGRADED worker earn promotion back."""

    def __init__(self, target_host: str, target_port: int,
                 spec: Optional[ChaosSpec] = None, name: str = ""):
        self.target = (target_host, int(target_port))
        self.name = name or f"{target_host}:{target_port}"
        self._spec = spec
        self._spec_lock = threading.Lock()
        self._conn_idx = 0
        self._stop = threading.Event()
        self._socks: List[socket.socket] = []
        self._socks_lock = threading.Lock()
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind(("127.0.0.1", 0))
        self._listener.listen(64)
        self.port = self._listener.getsockname()[1]
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True,
            name=f"srt-netchaos-{self.name}")
        self._accept_thread.start()

    # -- spec management -------------------------------------------------
    def set_spec(self, spec: Optional[ChaosSpec]) -> None:
        """Swap the injection plan; applies to NEW connections (the
        coordinator's always-evict-on-error pooling dials fresh ones),
        and existing pumps pick it up per chunk for the stall flag."""
        with self._spec_lock:
            self._spec = spec

    def clear(self) -> None:
        self.set_spec(None)

    # -- forwarding ------------------------------------------------------
    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                src, _addr = self._listener.accept()
            except OSError:
                return
            try:
                dst = socket.create_connection(self.target, timeout=10.0)
                dst.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                src.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            except OSError:
                # worker gone: the client sees EOF, exactly what a dead
                # backend looks like
                try:
                    src.close()
                except OSError:
                    pass
                continue
            with self._spec_lock:
                spec = self._spec
                idx = self._conn_idx
                self._conn_idx += 1
            inj = spec.spawn(idx) if spec is not None \
                else {d: None for d in DIRECTIONS}
            with self._socks_lock:
                self._socks += [src, dst]
            state: Dict = {}
            for a, b, d in ((src, dst, "c2w"), (dst, src, "w2c")):
                threading.Thread(
                    target=self._pump, args=(a, b, inj[d], state),
                    daemon=True,
                    name=f"srt-netchaos-{self.name}-{d}").start()

    def _pump(self, src: socket.socket, dst: socket.socket,
              inj: Optional[_Injection], state: Dict) -> None:
        try:
            while not self._stop.is_set():
                data = src.recv(1 << 16)
                if not data:
                    break
                if inj is None:
                    # a half-open trigger in the opposite direction
                    # stalls the whole connection — keep draining the
                    # sender (so it never learns) but forward nothing
                    if not state.get("stalled"):
                        dst.sendall(data)
                    continue
                out = inj.feed(data, state)
                if out:
                    dst.sendall(out)
            if inj is not None and not state.get("stalled"):
                tail = inj.flush()
                if tail:
                    dst.sendall(tail)
        except _ResetSignal:
            _rst_close(src)
            _rst_close(dst)
            return
        except OSError:
            pass
        for s in (src, dst):
            hard_close(s)

    def close(self) -> None:
        self._stop.set()
        # hard_close: a bare close() wakes neither the accept loop nor a
        # pump blocked in recv()
        hard_close(self._listener)
        with self._socks_lock:
            socks, self._socks = self._socks, []
        for s in socks:
            hard_close(s)
        self._accept_thread.join(2.0)


def interpose(coord, worker_id: str,
              spec: Optional[ChaosSpec] = None) -> NetChaosProxy:
    """Rewire one registered worker's data plane through a fresh chaos
    proxy: the coordinator's next op (and its liveness probes) dial the
    proxy instead of the worker.  Heartbeats ride the worker's OWN
    control connection and stay untouched — gray data plane, healthy
    control plane.  Returns the proxy (caller owns ``close()``)."""
    with coord._lock:
        w = coord._workers[worker_id]
        proxy = NetChaosProxy(w.host, w.data_port, spec, name=worker_id)
        w.host, w.data_port = "127.0.0.1", proxy.port
        stale = coord._conns.pop(worker_id, None)
    if stale is not None:
        try:
            stale.close()
        except OSError:
            pass
    return proxy
