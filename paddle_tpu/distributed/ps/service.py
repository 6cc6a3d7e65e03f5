"""Multi-host parameter-server transport.

Reference: the brpc PS generation —
  * paddle/fluid/distributed/service/brpc_ps_server.cc (RPC server:
    pull_sparse/push_sparse/save/load/stop handlers)
  * service/brpc_ps_client.cc (row→shard routing, request fan-out)
  * service/communicator.cc (client-side batching; the in-process
    AsyncCommunicator here plugs straight on top of RemoteEmbeddingTable)
  * operators/distributed/heart_beat_monitor.cc (worker liveness)

TPU-native scope: the *dense* path needs no PS at all (XLA collectives
over ICI/DCN own it), so this service carries only the host-tier sparse
tables (HostEmbeddingTable) that exceed HBM.  Transport is a
length-prefixed binary protocol over TCP — a JSON header plus raw
numpy buffers; no pickle on the wire, so a malicious peer can at worst
corrupt table values, not execute code.  Rows are sharded over servers
by ``id % n_servers`` (brpc_ps_client.cc's key-mod routing).
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import socketserver
import struct
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence

import numpy as np

from paddle_tpu.distributed.ps import HostEmbeddingTable
from paddle_tpu.distributed.ps.device_table import (
    WIRE_DTYPES, dequantize_rows, normalize_wire, quantize_rows)
from paddle_tpu.framework import (chaos, health, locks, monitor,
                                  observability)
from paddle_tpu.framework.flags import flag
from paddle_tpu.framework.observability import flight

__all__ = ["PsServer", "PsClient", "RemoteEmbeddingTable",
           "HeartBeatMonitor", "TransportStats", "serve"]


# ---------------------------------------------------------------------------
# wire protocol
# ---------------------------------------------------------------------------

def _recvall(sock: socket.socket, n: int) -> bytes:
    chunks = []
    while n:
        b = sock.recv(min(n, 1 << 20))
        if not b:
            raise ConnectionError("peer closed")
        chunks.append(b)
        n -= len(b)
    return b"".join(chunks)


def _frame_msg(header: dict, bufs: Sequence[np.ndarray] = ()) -> bytes:
    """Serialize one wire frame (header json + raw buffers)."""
    meta = dict(header)
    meta["__bufs__"] = [{"shape": list(b.shape), "dtype": str(b.dtype)}
                        for b in bufs]
    hb = json.dumps(meta).encode()
    out = [struct.pack("<I", len(hb)), hb]
    for b in bufs:
        data = np.ascontiguousarray(b).tobytes()
        out.append(struct.pack("<Q", len(data)))
        out.append(data)
    return b"".join(out)


def _send_msg(sock: socket.socket, header: dict,
              bufs: Sequence[np.ndarray] = ()) -> int:
    """Frame + send; returns the bytes put on the wire (transport
    accounting)."""
    msg = _frame_msg(header, bufs)
    sock.sendall(msg)
    return len(msg)


def _recv_msg(sock: socket.socket):
    """Returns ``(header, bufs, wire_bytes)``."""
    (hlen,) = struct.unpack("<I", _recvall(sock, 4))
    header = json.loads(_recvall(sock, hlen))
    nbytes = 4 + hlen
    bufs = []
    for spec in header.pop("__bufs__", []):
        (blen,) = struct.unpack("<Q", _recvall(sock, 8))
        raw = _recvall(sock, blen)
        nbytes += 8 + blen
        bufs.append(np.frombuffer(raw, dtype=np.dtype(spec["dtype"]))
                    .reshape(spec["shape"]).copy())
    return header, bufs, nbytes


class TransportStats:
    """Measured transport counters for one PS peer (client or server):
    RPC count, wire bytes each way, and a per-op latency histogram —
    wired into the process-wide monitor registry (``ps_<role>_*`` stats
    and histograms) so the observability layer sees every peer, while
    each instance keeps its own numbers, so a caller can report the
    *measured* wire MB/step of one client rather than the analytic
    formula."""

    # distinct op keys are capped: the op string arrives off the wire
    # unvalidated, and a junk-sending peer must not grow per-op dicts
    # and process-global histograms without bound on a long-lived shard
    MAX_OPS = 32

    def __init__(self, role: str = "client"):
        self.role = role
        self._lock = locks.lock("ps.transport.stats")
        self.rpcs = 0
        self.errors = 0
        self.bytes_sent = 0
        self.bytes_recv = 0
        self._per_op: Dict[str, Dict[str, int]] = {}
        self._lat: Dict[str, monitor.Histogram] = {}

    def record(self, op: str, sent: int, recv: int, seconds: float,
               error: bool = False):
        op = op or "?"
        ms = seconds * 1e3
        with self._lock:
            # cap enforced under the lock; the last slot is reserved
            # for the 'other' bucket so the bound holds exactly
            if op != "other" and op not in self._per_op and \
                    len(self._per_op) >= self.MAX_OPS - 1:
                op = "other"
            self.rpcs += 1
            self.errors += int(error)
            self.bytes_sent += sent
            self.bytes_recv += recv
            o = self._per_op.setdefault(
                op, {"rpcs": 0, "errors": 0, "bytes_sent": 0,
                     "bytes_recv": 0})
            o["rpcs"] += 1
            o["errors"] += int(error)
            o["bytes_sent"] += sent
            o["bytes_recv"] += recv
            h = self._lat.get(op)
            if h is None:
                h = self._lat[op] = monitor.Histogram(
                    f"ps_{self.role}_rpc_ms_{op}")
        h.record(ms)
        monitor.stat_add(f"ps_{self.role}_rpcs")
        monitor.stat_add(f"ps_{self.role}_bytes_sent", sent)
        monitor.stat_add(f"ps_{self.role}_bytes_recv", recv)
        if error:
            monitor.stat_add(f"ps_{self.role}_rpc_errors")
        monitor.observe(f"ps_{self.role}_rpc_ms_{op}", ms)
        if self.role == "client":
            # every client-side RPC latency feeds the health plane's
            # straggler/storm detector (one stream across ops — an
            # injected ps.rpc latency or a slow peer trips it)
            health.observe("ps_rpc_ms", ms)

    def snapshot(self) -> dict:
        with self._lock:
            return {"role": self.role, "rpcs": self.rpcs,
                    "errors": self.errors,
                    "bytes_sent": self.bytes_sent,
                    "bytes_recv": self.bytes_recv,
                    "per_op": {k: dict(v)
                               for k, v in self._per_op.items()},
                    "latency_ms": {k: h.summary()
                                   for k, h in self._lat.items()}}


# ---------------------------------------------------------------------------
# heartbeat (heart_beat_monitor.cc)
# ---------------------------------------------------------------------------

class HeartBeatMonitor:
    """Tracks last-beat time per worker; a worker silent for longer than
    ``timeout`` is reported dead (heart_beat_monitor.cc:56 LostWorkerMonitor
    loop, with the thread made optional).

    Death is not permanent: a beat from a reported-dead worker *revives*
    it — and counts a **flap** (dead→alive transition, surfaced via
    ``flap_count``/``on_revive``) so the elastic agent can tell a flaky
    worker (restartable, but burn its retry budget) from a gone one
    (expire its lease, shrink the job)."""

    def __init__(self, timeout: float = 30.0):
        self.timeout = timeout
        self._beats: Dict[str, float] = {}
        self._lock = locks.lock("ps.heartbeat")
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.on_dead = None            # callback(worker_id)
        self.on_revive = None          # callback(worker_id, flap_count)
        self._reported: set = set()
        self._flaps: Dict[str, int] = {}

    def beat(self, worker: str):
        with self._lock:
            was_dead = worker in self._reported
            self._beats[worker] = time.monotonic()
            self._reported.discard(worker)
            if was_dead:
                self._flaps[worker] = self._flaps.get(worker, 0) + 1
                flaps = self._flaps[worker]
        if was_dead and self.on_revive is not None:
            self.on_revive(worker, flaps)

    def flap_count(self, worker: str) -> int:
        """dead→alive transitions seen for this worker (0 = never died
        or never came back)."""
        with self._lock:
            return self._flaps.get(worker, 0)

    def mark_dead(self, worker: str):
        """Force-report a peer dead NOW (no timeout wait) — the PS client
        calls this when an endpoint exhausts its RPC retries, so transport
        death surfaces through the same channel as heartbeat silence."""
        with self._lock:
            self._beats[worker] = time.monotonic() - (self.timeout + 1.0)
            already = worker in self._reported
            self._reported.add(worker)
        if not already and self.on_dead is not None:
            self.on_dead(worker)

    def workers(self) -> Dict[str, float]:
        now = time.monotonic()
        with self._lock:
            return {w: now - t for w, t in self._beats.items()}

    def dead_workers(self) -> List[str]:
        return [w for w, age in self.workers().items()
                if age > self.timeout]

    def _loop(self, interval: float):
        while not self._stop.wait(interval):
            for w in self.dead_workers():
                if w not in self._reported:
                    self._reported.add(w)
                    if self.on_dead is not None:
                        self.on_dead(w)

    def start(self, interval: float = 1.0):
        self._thread = threading.Thread(target=self._loop, args=(interval,),
                                        daemon=True)
        self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

class _Handler(socketserver.BaseRequestHandler):
    def handle(self):
        srv: "PsServer" = self.server.ps          # type: ignore
        sock = self.request
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        while True:
            try:
                header, bufs, n_in = _recv_msg(sock)
            except (ConnectionError, OSError):
                return
            t0 = time.perf_counter()
            ok = True
            # re-open the client's trace server-side: a request carrying
            # trace/span ids gets a child span around the op handling, so
            # the merged timeline shows the server work under the RPC
            # that caused it
            ctx = srv.tracer.extract(header)
            span = srv.tracer.start_span(
                f"ps.server.{header.get('op')}", parent=ctx, detached=True,
                attrs={"worker": header.get("worker")}) \
                if ctx is not None else None
            try:
                reply, rbufs = srv._dispatch(header, bufs)
                ok = reply.get("ok", False)
            except Exception as e:                # noqa: BLE001
                reply, rbufs, ok = {"ok": False, "error": repr(e)}, [], False
            if span is not None:
                span.end(status="ok" if ok else "error")
            # record BEFORE the reply bytes hit the wire: a client that
            # snapshots the instant its reply arrives (tests, stat-op
            # consumers) must find this request already counted — the
            # old record-after-send ordering raced exactly that read
            msg = _frame_msg(reply, rbufs)
            srv.transport.record(header.get("op"), len(msg), n_in,
                                 time.perf_counter() - t0, error=not ok)
            try:
                sock.sendall(msg)
            except OSError:
                return
            if header.get("op") in ("bye", "shutdown"):
                return


class _TcpServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


class PsServer:
    """One PS shard: serves pull/push/heartbeat/state for its tables
    (brpc_ps_server.cc handler table, minus the brpc dependency)."""

    # remembered (worker, seq) stamps per worker — enough to absorb any
    # realistic retry window while bounding memory for long jobs
    PUSH_SEQ_WINDOW = 4096

    def __init__(self, tables: Dict[str, HostEmbeddingTable],
                 host: str = "127.0.0.1", port: int = 0,
                 heartbeat_timeout: float = 30.0,
                 n_workers: Optional[int] = None,
                 tracer: Optional[observability.Tracer] = None):
        self.tables = tables
        # instance tracer for in-process multi-role runs (one span file
        # per logical process); the module singleton otherwise
        self.tracer = tracer if tracer is not None else observability.tracer
        self.monitor = HeartBeatMonitor(heartbeat_timeout)
        self.n_workers = n_workers
        self.epoch = 0                 # membership-epoch fence (elastic)
        self._bye_count = 0
        self._lock = locks.lock("ps.server.state")
        self.transport = TransportStats(role="server")
        # per-table request accounting (the PS-skew telemetry the
        # cluster collector aggregates per shard): pulls/pushes served
        # and row volume each way, plus the table's own bounded hot-row
        # sketch — see HostEmbeddingTable.hot_rows
        self._table_stats: Dict[str, Dict[str, int]] = {}
        self._tstats_lock = locks.lock("ps.server.table_stats")
        # push dedup: worker -> insertion-ordered {seq: True} window
        self._push_seen: Dict[str, "dict"] = {}
        self._seen_lock = locks.lock("ps.server.push_seen")
        self._tcp = _TcpServer((host, port), _Handler)
        self._tcp.ps = self                        # type: ignore
        self.host, self.port = self._tcp.server_address
        self._thread: Optional[threading.Thread] = None

    # -- request dispatch ---------------------------------------------------
    _FENCED_OPS = ("push", "push_pull", "load_state")

    # remembered worker identities are bounded too: elastic churn mints
    # a fresh worker id per restart, and a shard must not grow a dedup
    # window per dead worker forever
    PUSH_SEQ_WORKERS = 256

    def _reserve_push(self, header: dict) -> bool:
        """Atomically claim this (worker, seq) stamp — the retried-push
        double-apply guard.  Returns False when the stamp is already
        claimed: either the push was applied, or another handler thread
        is applying it RIGHT NOW (a retry racing a slow apply must not
        land a second copy).  A FAILED apply rolls its claim back via
        :meth:`_unreserve_push` so a later retry still lands.
        Unstamped pushes (old clients) always pass."""
        worker, seq = header.get("worker"), header.get("seq")
        if worker is None or seq is None:
            return True
        with self._seen_lock:
            # re-insert → LRU order, so the worker-count cap below
            # evicts the longest-quiet identity, not an active one
            seen = self._push_seen.pop(worker, None)
            if seen is None:
                seen = {}
            self._push_seen[worker] = seen
            if seq in seen:
                return False
            seen[seq] = True
            while len(seen) > self.PUSH_SEQ_WINDOW:
                seen.pop(next(iter(seen)))
            while len(self._push_seen) > self.PUSH_SEQ_WORKERS:
                self._push_seen.pop(next(iter(self._push_seen)))
        return True

    def _unreserve_push(self, header: dict):
        worker, seq = header.get("worker"), header.get("seq")
        with self._seen_lock:
            self._push_seen.get(worker, {}).pop(seq, None)

    def _note_table(self, table: str, pulls: int = 0, pushes: int = 0,
                    rows_pulled: int = 0, rows_pushed: int = 0):
        with self._tstats_lock:
            t = self._table_stats.setdefault(
                table, {"pulls": 0, "pushes": 0, "rows_pulled": 0,
                        "rows_pushed": 0})
            t["pulls"] += pulls
            t["pushes"] += pushes
            t["rows_pulled"] += rows_pulled
            t["rows_pushed"] += rows_pushed
        if pulls:
            monitor.stat_add(f"ps_server_table_pulls[{table}]", pulls)
        if pushes:
            monitor.stat_add(f"ps_server_table_pushes[{table}]", pushes)

    def table_telemetry(self) -> Dict[str, dict]:
        """Per-table request counts + the bounded hot-row top-k — the
        ``tables`` section of this shard's collector pushes and of the
        ``stat`` op (the skew/hot-row telemetry a serving-side row
        cache and the cluster view consume)."""
        with self._tstats_lock:
            out = {n: dict(t) for n, t in self._table_stats.items()}
        for name, t in self.tables.items():
            sketch = getattr(t, "hot_rows", None)
            if sketch is not None:
                out.setdefault(name, {"pulls": 0, "pushes": 0,
                                      "rows_pulled": 0,
                                      "rows_pushed": 0})
                out[name]["hot_rows"] = sketch.top()
        return out

    def _is_dup_push(self, header: dict) -> bool:
        """Peek: stamp already claimed? (Test/introspection surface —
        the apply path uses the atomic reserve/unreserve pair.)"""
        worker, seq = header.get("worker"), header.get("seq")
        with self._seen_lock:
            return seq is not None and \
                seq in self._push_seen.get(worker, ())

    def _apply_push(self, header: dict, ids: np.ndarray, grad_bufs):
        """Dedup-guarded push: decode the (possibly quantized) gradient
        rows and apply them, unless the stamp was already claimed."""
        if not self._reserve_push(header):
            return True
        try:
            t = self.tables[header["table"]]
            grads = dequantize_rows(grad_bufs, header.get("wire", "f32"),
                                    cols=int(header.get("cols", 0) or 0))
            t.push(ids.astype(np.int64), grads, lr=header.get("lr"))
        except Exception:
            self._unreserve_push(header)   # failed apply frees the stamp
            raise
        return False

    def _dispatch(self, header: dict, bufs):
        op = header.get("op")
        # membership-epoch fencing (elastic re-form): a worker still
        # running under a pre-bump epoch must not mutate tables the
        # survivors have re-formed — its pushes are rejected hard (the
        # client surfaces this as a non-retried RuntimeError).  Once a
        # fence is installed (epoch > 0) an UNSTAMPED mutation is equally
        # stale — every live worker of a fenced job adopted an epoch at
        # its last re-form; epochless clients stay compatible only while
        # the job has never fenced.  Reads stay open: a stale pull is
        # harmless and the worker needs its error path, not a hang.
        we = header.get("epoch")
        if op in self._FENCED_OPS and self.epoch > 0 and \
                (we is None or we < self.epoch):
            flight.record("ps.fence_rejected", severity="warn", op=op,
                          worker=header.get("worker"), worker_epoch=we,
                          server_epoch=self.epoch)
            return {"ok": False,
                    "error": f"stale membership epoch {we} < {self.epoch}"
                             " — the job re-formed without this worker; "
                             "rejoin and refresh before pushing"}, []
        if op == "set_epoch":
            with self._lock:
                e = int(header["epoch"])
                if header.get("n_workers") is not None and e >= self.epoch:
                    # the re-form carries the new world size: the bye
                    # quorum must follow a shrink, or the server waits
                    # forever for byes from workers that no longer
                    # exist.  Gated on the epoch so a slower survivor's
                    # STALE re-form cannot overwrite a newer quorum.
                    self.n_workers = int(header["n_workers"])
                if e > self.epoch:
                    # a NEW generation discards byes banked under the
                    # previous one — only its own survivors' byes may
                    # tip the quorum.  Strictly greater: the second
                    # survivor installing the SAME epoch must not wipe
                    # byes its peers already banked under it.
                    self._bye_count = 0
                self.epoch = max(self.epoch, e)
            return {"ok": True, "epoch": self.epoch,
                    "n_workers": self.n_workers}, []
        if op == "hello":
            # wire-dtype handshake: echo the negotiated encoding.  An
            # OLD server never reaches here (unknown op -> error), which
            # the client reads as "f32 only" — old/new peers always
            # interoperate at exact-parity f32.
            try:
                wire = normalize_wire(header.get("wire", "f32"))
            except ValueError:
                wire = "f32"
            # "time" rides the handshake so a client can estimate this
            # server's clock offset (PsClient.sync_clock) — what
            # trace_merge uses to land every process on one timeline
            return {"ok": True, "wire": wire,
                    "wire_dtypes": list(WIRE_DTYPES),
                    "time": time.time()}, []
        if op == "pull":
            t = self.tables[header["table"]]
            ids = bufs[0].astype(np.int64)
            rows = t.pull(ids)
            self._note_table(header["table"], pulls=1,
                             rows_pulled=int(ids.size))
            # reply-driven negotiation: encode in the dtype the request
            # asked for and DECLARE it in the reply header; a client
            # talking to an old server sees no "wire" key and decodes
            # f32 — no separate handshake needed on the pull side.
            # (int4 requests only arrive hello-gated: an old server's
            # normalize_wire would error this path, so the client pins
            # f32 unless the handshake listed int4.)  Packed int4
            # replies declare the logical row width — the packed buffer
            # alone cannot distinguish an odd dim from its pad nibble
            wire = normalize_wire(header.get("wire", "f32"))
            hdr = {"ok": True, "wire": wire}
            if wire == "int4":
                hdr["cols"] = int(rows.shape[-1])
            return hdr, quantize_rows(rows, wire)
        if op == "push":
            dup = self._apply_push(header, bufs[0], bufs[1:])
            self._note_table(header["table"], pushes=1,
                             rows_pushed=int(np.asarray(bufs[0]).size))
            return {"ok": True, "dup": dup}, []
        if op == "push_pull":
            # one round-trip for the pipeline's coalesced cycle: apply
            # the previous step's gradient rows (dedup-guarded — a
            # retry must not double-apply), then serve the next step's
            # pull.  The pull half is idempotent, so a retried
            # push_pull whose push was deduped still returns rows.
            n_push = int(header.get("n_push_bufs", 0))
            dup = False
            if n_push:
                dup = self._apply_push(header, bufs[0], bufs[1:1 + n_push])
            t = self.tables[header["table"]]
            pull_ids = bufs[1 + n_push].astype(np.int64)
            rows = t.pull(pull_ids)
            self._note_table(
                header["table"], pulls=1, pushes=int(bool(n_push)),
                rows_pulled=int(pull_ids.size),
                rows_pushed=int(np.asarray(bufs[0]).size) if n_push
                else 0)
            wire = normalize_wire(header.get("wire", "f32"))
            hdr = {"ok": True, "wire": wire, "dup": dup}
            if wire == "int4":
                hdr["cols"] = int(rows.shape[-1])
            return hdr, quantize_rows(rows, wire)
        if op == "graph":
            # GNN tier: delegate to GraphTable.dispatch (graph_brpc_server
            # sample_neighbors / node_feat / degree ops)
            return self.tables[header["table"]].dispatch(header, bufs)
        if op == "heartbeat":
            self.monitor.beat(header["worker"])
            return {"ok": True, "time": time.time()}, []
        if op == "state":
            t = self.tables[header["table"]]
            d = t.state_dict()
            arrs = [np.asarray(d["table"])]
            has_g2 = "g2" in d
            if has_g2:
                arrs.append(np.asarray(d["g2"]))
            return {"ok": True, "optimizer": d["optimizer"],
                    "has_g2": has_g2}, arrs
        if op == "load_state":
            t = self.tables[header["table"]]
            d = {"table": bufs[0], "optimizer": header["optimizer"]}
            if header.get("has_g2"):
                d["g2"] = bufs[1]
            t.set_state_dict(d)
            return {"ok": True}, []
        if op == "stat":
            return {"ok": True,
                    "tables": {n: {"rows": getattr(t, "num_embeddings", 0),
                                   "dim": getattr(t, "embedding_dim", 0)}
                               for n, t in self.tables.items()},
                    "workers": self.monitor.workers(),
                    "dead": self.monitor.dead_workers(),
                    "flaps": {w: self.monitor.flap_count(w)
                              for w in self.monitor.workers()},
                    "wire_dtypes": list(WIRE_DTYPES),
                    "transport": self.transport.snapshot(),
                    "flight": flight.recent(32),
                    # detector + compile-site state, so a worker set can
                    # spot its straggler from one stat() call
                    "health": health.snapshot(),
                    # per-table request skew + hot-row top-k — what
                    # cluster_top's collector-less fallback scrapes
                    "table_stats": self.table_telemetry(),
                    "epoch": self.epoch}, []
        if op == "bye":
            # a fenced job counts only CURRENT-epoch byes toward the
            # shutdown quorum: an evicted stale worker's graceful exit
            # must not tip a shrunk quorum and kill the servers under
            # the survivors still training.  (Reply ok either way — the
            # stale worker is leaving, which is exactly what we want.)
            stale = self.epoch > 0 and (we is None or we < self.epoch)
            done = False
            with self._lock:
                if not stale:
                    self._bye_count += 1
                if self.n_workers and self._bye_count >= self.n_workers:
                    done = True
            if done:
                threading.Thread(target=self.shutdown, daemon=True).start()
            return {"ok": True, "stale": stale, "remaining":
                    (self.n_workers - self._bye_count)
                    if self.n_workers else -1}, []
        if op == "shutdown":
            threading.Thread(target=self.shutdown, daemon=True).start()
            return {"ok": True}, []
        return {"ok": False, "error": f"unknown op {op!r}"}, []

    # -- lifecycle ----------------------------------------------------------
    def start(self):
        """Serve on a background thread (fleet.run_server uses the blocking
        form)."""
        self.monitor.start()
        self._thread = threading.Thread(target=self._tcp.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self

    def serve_forever(self):
        self.monitor.start()
        self._tcp.serve_forever()

    def shutdown(self):
        self.monitor.stop()
        self._tcp.shutdown()
        self._tcp.server_close()
        if self._thread is not None:
            self._thread.join(timeout=2.0)


# ---------------------------------------------------------------------------
# client
# ---------------------------------------------------------------------------

class _Conn:
    def __init__(self, endpoint: str, timeout: Optional[float] = None,
                 stats: Optional[TransportStats] = None):
        self.endpoint = endpoint
        host, port = endpoint.rsplit(":", 1)
        self._addr = (host, int(port))
        self.timeout = float(flag("ps_rpc_timeout")) if timeout is None \
            else timeout
        self.stats = stats
        self.lock = locks.lock("ps.conn")
        # first dial is best-effort: a client may legitimately be built
        # over a server set containing dead peers (elastic re-shard
        # probing survivors) — rpc() redials lazily and its retry path
        # owns the failure
        try:
            self.sock = self._connect()
        except OSError:
            self.sock = None

    def _connect(self):
        sock = socket.create_connection(self._addr, timeout=self.timeout)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return sock

    def rpc(self, header: dict, bufs=()):
        # injected drops/latency fire BEFORE the send (and before the
        # lock), so a retried call cannot double-apply a non-idempotent
        # push and an injected drop never desyncs a healthy socket.
        # The timing window opens here too: an injected latency is a
        # slow network, and the histograms should say so.
        t0 = time.perf_counter()
        sent = rcvd = 0
        try:
            chaos.fault_point("ps.rpc",  # pta: disable=PTA301 (PsClient.call owns retry/backoff + mark_dead)
                              meta={"op": header.get("op"),
                                    "endpoint": self.endpoint})
            with self.lock:
                if self.sock is None:
                    self.sock = self._connect()  # lazy redial after failure
                try:
                    sent = _send_msg(self.sock, header, bufs)
                    reply, rbufs, rcvd = _recv_msg(self.sock)  # pta: disable=PTA402 (the per-connection lock IS the stream owner: it serializes request/reply framing so a concurrent caller can never read another RPC's reply; FLAGS_ps_rpc_timeout bounds the recv)
                except (ConnectionError, OSError):
                    # the stream may be mid-message: invalidate UNDER the
                    # lock so no concurrent caller (e.g. the heartbeat
                    # thread vs a pull fan-out) can ever read a stale
                    # partial reply as its own
                    try:
                        self.sock.close()
                    except OSError:
                        pass
                    self.sock = None
                    raise
        except (ConnectionError, OSError):
            if self.stats is not None:
                self.stats.record(header.get("op"), sent, rcvd,
                                  time.perf_counter() - t0, error=True)
            raise
        if self.stats is not None:
            self.stats.record(header.get("op"), sent, rcvd,
                              time.perf_counter() - t0,
                              error=not reply.get("ok", False))
        if not reply.get("ok", False):
            raise RuntimeError(f"ps rpc {header.get('op')} failed: "
                               f"{reply.get('error')}")
        return reply, rbufs

    def close(self):
        if self.sock is None:          # invalidated by a failed rpc
            return
        try:
            self.sock.close()
        except OSError:
            pass


class PsClient:
    """Routes rows to shards by ``id % n_servers`` and fans requests out in
    parallel (brpc_ps_client.cc pull_sparse semantics).

    Transport failures (dropped connection, timeout, injected ``ps.rpc``
    chaos) are retried with exponential backoff — ``sleep(backoff_base *
    2^attempt)`` between attempts, the socket redialed each time — up to
    ``max_retries`` retries per RPC (FLAGS_ps_rpc_max_retries /
    FLAGS_ps_rpc_backoff_base / FLAGS_ps_rpc_timeout).  An endpoint that
    exhausts its retries is appended to ``dead_endpoints``, reported to
    the optional ``monitor`` (HeartBeatMonitor.mark_dead) and to the
    ``on_endpoint_dead`` callback, then the error propagates — the same
    lost-peer channel heart_beat_monitor.cc feeds.  Application-level
    errors (server replied ok=False) are NOT retried.

    Retry idempotence: a retry re-sends only when the previous attempt
    failed before a reply was read.  ``pull`` is idempotent anyway; a
    ``push`` whose reply was lost after the server started (or
    finished) applying it is caught by the server's ``(worker, seq)``
    stamp reservation — every push (and the push half of
    ``push_pull``) carries a monotonically increasing sequence number,
    the retry re-sends the SAME stamp, and the server atomically
    claims a stamp before applying (so a retry racing a still-running
    apply is also rejected); only a FAILED apply rolls the claim back
    so that retry can land.

    Wire dtype: pull replies and push gradient rows travel in
    ``wire_dtype`` (FLAGS_ps_wire_dtype; 'bf16' default, 'int8' adds a
    per-row scale, 'int4' packs two nibbles per byte + per-row scale,
    'f32' is the exact-parity fallback).  bf16/int8 pulls are
    reply-driven (the server declares the encoding it used); int4
    pulls and all quantized pushes engage only after a ``hello``
    handshake confirmed the server lists the dtype — so an old peer on
    either side degrades the link to f32 instead of corrupting it."""

    def __init__(self, endpoints: Sequence[str],
                 worker_id: Optional[str] = None,
                 monitor: Optional[HeartBeatMonitor] = None,
                 max_retries: Optional[int] = None,
                 backoff_base: Optional[float] = None,
                 timeout: Optional[float] = None,
                 wire_dtype: Optional[str] = None,
                 tracer: Optional[observability.Tracer] = None):
        self.tracer = tracer if tracer is not None else observability.tracer
        self.transport = TransportStats(role="client")
        self.endpoints = list(endpoints)
        self._conns = [_Conn(ep, timeout=timeout, stats=self.transport)
                       for ep in self.endpoints]
        self._pool = ThreadPoolExecutor(max_workers=max(
            2, len(self.endpoints)))
        self.worker_id = worker_id or f"worker-{os.getpid()}"
        self.epoch: Optional[int] = None   # membership epoch (elastic)
        self.monitor = monitor
        self.max_retries = int(flag("ps_rpc_max_retries")) \
            if max_retries is None else int(max_retries)
        self.backoff_base = float(flag("ps_rpc_backoff_base")) \
            if backoff_base is None else float(backoff_base)
        self.wire_dtype = normalize_wire(
            flag("ps_wire_dtype") if wire_dtype is None else wire_dtype)
        self._push_wires: Dict[int, str] = {}  # negotiated, per server
        self._dims: Dict[str, int] = {}        # table dim cache
        # dedup stamps are scoped to this client INCARNATION, not the
        # worker id: a re-built client (elastic re-form, restart under
        # the same rank/pid) restarts _seq at 0, and colliding with the
        # previous incarnation's window on a surviving server would
        # silently drop its first pushes as duplicates
        self._push_ident = f"{self.worker_id}~{os.urandom(4).hex()}"
        self._seq = 0
        self._seq_lock = locks.lock("ps.client.seq")
        self.dead_endpoints: List[str] = []
        self._dead_lock = locks.lock("ps.client.dead")
        self.on_endpoint_dead = None       # callback(endpoint, exception)
        self._hb_stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        if self.tracer.enabled:
            # best-effort clock sync so this process's span file carries
            # a measured offset to the server clock before any span is
            # written; dead/old peers are fine (a tracer with offset 0
            # merges untranslated — same as before)
            try:
                self.sync_clock()
            except (ConnectionError, OSError, RuntimeError):
                pass

    @property
    def n(self):
        return len(self._conns)

    # -- retrying transport -------------------------------------------------
    def _rpc(self, s: int, header: dict, bufs=(),
             retries: Optional[int] = None, links=None):
        conn, ep = self._conns[s], self.endpoints[s]
        op = header.get("op")
        if self.epoch is not None:
            header.setdefault("epoch", self.epoch)
        retries = self.max_retries if retries is None else retries
        last: Optional[Exception] = None
        # one logical span per RPC; each ATTEMPT is a child with a fresh
        # span id under the same trace id (the retry contract), and the
        # attempt's context rides the header so the server's child span
        # links to exactly the attempt that reached it.  ``links`` are
        # caller-declared causal edges stamped onto the logical span —
        # the coalesced deferred push's "this RPC carries step N's
        # gradient" edge (PSTrainStep threads it through push/push_pull)
        root = self.tracer.start_span(f"ps.{op}", detached=True,
                                      attrs={"endpoint": ep})
        for lk in links or ():
            root.link(lk.get("span"), lk.get("kind", "link"))
        for attempt in range(retries + 1):
            asp = self.tracer.start_span(
                "ps.rpc", parent=root, detached=True,
                attrs={"op": op, "endpoint": ep, "attempt": attempt})
            self.tracer.inject(header, asp)
            try:
                reply, rbufs = conn.rpc(header, bufs)
                asp.end(status="ok")
                root.end(status="ok")
                with self._dead_lock:              # recovered
                    if ep in self.dead_endpoints:
                        self.dead_endpoints.remove(ep)
                if self.monitor is not None:
                    self.monitor.beat(ep)
                return reply, rbufs
            except RuntimeError as e:      # server-side error: don't retry
                asp.end(status="error", exc=repr(e))
                root.end(status="error")
                raise
            except (ConnectionError, OSError) as e:
                last = e
                asp.end(status="error", exc=repr(e))
                flight.record("ps.retry", severity="warn", op=op,
                              endpoint=ep, attempt=attempt,
                              will_retry=attempt < retries, exc=repr(e))
                if attempt < retries:
                    # conn.rpc invalidated the socket; the next attempt
                    # redials lazily under the connection lock
                    time.sleep(self.backoff_base * (2 ** attempt))
        root.end(status="error", exc=repr(last))
        self._report_dead(ep, last)
        raise ConnectionError(
            f"ps endpoint {ep} dead after {retries + 1} attempts "
            f"of {header.get('op')!r}: {last!r}")

    def _report_dead(self, endpoint: str, exc: Optional[Exception]):
        flight.record("ps.mark_dead", severity="error", endpoint=endpoint,
                      exc=repr(exc))
        with self._dead_lock:
            if endpoint not in self.dead_endpoints:
                self.dead_endpoints.append(endpoint)
        if self.monitor is not None:
            self.monitor.mark_dead(endpoint)
        if self.on_endpoint_dead is not None:
            self.on_endpoint_dead(endpoint, exc)

    # -- wire dtype negotiation / push stamping -----------------------------
    def _next_seq(self) -> int:
        with self._seq_lock:
            self._seq += 1
            return self._seq

    def _push_wire(self, s: int) -> str:
        """Negotiated dtype for rows this client SENDS to server ``s``
        (push gradients).  Resolved once per server via the ``hello``
        handshake; an old server that doesn't know the op pins the link
        to f32.  (Pulls need no handshake — the reply header declares
        its own encoding.)"""
        w = self._push_wires.get(s)
        if w is None:
            if self.wire_dtype == "f32":
                w = "f32"
            else:
                try:
                    reply, _ = self._rpc(
                        s, {"op": "hello", "wire": self.wire_dtype})
                    w = reply.get("wire", "f32") \
                        if self.wire_dtype in reply.get("wire_dtypes", ()) \
                        else "f32"
                except RuntimeError:       # old server: unknown op
                    w = "f32"
            self._push_wires[s] = w
        return w

    def _pull_wire(self, s: int) -> str:
        """Wire dtype to ASK server ``s`` to encode pull replies in.
        bf16/int8 stay reply-driven (any server that predates them
        simply ignores unknown reply preferences at f32... they are in
        the frozen-era set, every server decodes them).  int4 — the
        first dtype added AFTER the pull protocol shipped — must ride
        the ``hello`` handshake instead: an old server's pull path
        *raises* on a dtype it doesn't know, so the client pins f32
        unless the server's advertised ``wire_dtypes`` lists int4."""
        if self.wire_dtype != "int4":
            return self.wire_dtype
        return self._push_wire(s)

    def _decode_pull(self, table: str, reply: dict, rbufs) -> np.ndarray:
        rows = dequantize_rows(rbufs, reply.get("wire", "f32"),
                               cols=int(reply.get("cols", 0) or 0))
        self._dims[table] = rows.shape[-1]
        return rows

    # -- sparse ops ---------------------------------------------------------
    def table_dim(self, table: str) -> int:
        """Row dim of ``table``, cached after the first pull/stat — the
        empty-batch pull path must not burn a whole stat() RPC per call
        just to re-learn a constant."""
        dim = self._dims.get(table)
        if dim is None:
            dim = self._dims[table] = self.stat()["tables"][table]["dim"]
        return dim

    def pull(self, table: str, ids: np.ndarray) -> np.ndarray:
        ids = np.asarray(ids, np.int64)
        flat = ids.reshape(-1)
        owner = flat % self.n

        tctx = self.tracer.current()    # fan-out threads inherit the
                                        # caller's span as parent

        def one(s):
            mask = owner == s
            if not mask.any():
                return s, mask, None
            with self.tracer.activate(tctx):
                reply, rows = self._rpc(
                    s, {"op": "pull", "table": table,
                        "wire": self._pull_wire(s)}, [flat[mask]])
            return s, mask, self._decode_pull(table, reply, rows)

        first_dim = None
        parts = list(self._pool.map(one, range(self.n)))
        for _, _, rows in parts:
            if rows is not None:
                first_dim = rows.shape[1]
                break
        if first_dim is None:      # empty batch: cached table dim
            first_dim = self.table_dim(table)
        out = np.empty((flat.size, first_dim), np.float32)
        for _, mask, rows in parts:
            if rows is not None:
                out[mask] = rows
        return out.reshape(ids.shape + (first_dim,))

    def push(self, table: str, ids: np.ndarray, grads: np.ndarray,
             lr: Optional[float] = None, seq: Optional[int] = None,
             links=None):
        """``seq`` reuses a previously allocated stamp — the REPLAY path
        of a coalesced push whose first attempt may or may not have
        landed; the server's dedup then absorbs the copy that did.  A
        fresh stamp is minted when None (the normal case).  ``links``
        (``[{"span", "kind"}]``) stamp causal edges onto each shard
        RPC's logical span — see :meth:`_rpc`."""
        ids = np.asarray(ids, np.int64)
        flat = ids.reshape(-1)
        g = np.asarray(grads, np.float32).reshape(flat.size, -1)
        owner = flat % self.n
        seq = self._next_seq() if seq is None else seq

        tctx = self.tracer.current()

        def one(s):
            mask = owner == s
            if mask.any():
                with self.tracer.activate(tctx):
                    wire = self._push_wire(s)
                    hdr = {"op": "push", "table": table, "lr": lr,
                           "wire": wire, "worker": self._push_ident,
                           "seq": seq}
                    if wire == "int4":   # packed rows: declare width
                        hdr["cols"] = int(g.shape[-1])
                    self._rpc(s, hdr,
                              [flat[mask]] + quantize_rows(g[mask], wire),
                              links=links)

        list(self._pool.map(one, range(self.n)))

    def push_pull(self, table: str, push_ids: Optional[np.ndarray],
                  push_grads: Optional[np.ndarray],
                  pull_ids: np.ndarray,
                  lr: Optional[float] = None,
                  seq: Optional[int] = None,
                  links=None) -> np.ndarray:
        """Coalesced cycle: apply one batch's gradient rows AND fetch the
        next batch's rows in a single round-trip per shard (the
        DownpourWorker amortization — push(N) rides pull(N+1)'s RPC).
        ``push_ids``/``push_grads`` may be None for a pull-only call;
        ``seq`` as in :meth:`push`; ``links`` stamp causal edges
        (``deferred_push``: the step span whose gradient this RPC
        carries) onto each shard RPC's logical span."""
        pull_ids = np.asarray(pull_ids, np.int64)
        pflat = pull_ids.reshape(-1)
        powner = pflat % self.n
        if push_ids is None or len(np.asarray(push_ids)) == 0:
            return self.pull(table, pull_ids)
        gids = np.asarray(push_ids, np.int64).reshape(-1)
        g = np.asarray(push_grads, np.float32).reshape(gids.size, -1)
        gowner = gids % self.n
        seq = self._next_seq() if seq is None else seq

        tctx = self.tracer.current()

        def one(s):
            pmask = powner == s
            gmask = gowner == s
            if not pmask.any() and not gmask.any():
                return s, pmask, None
            with self.tracer.activate(tctx):
                if not pmask.any():            # push-only shard
                    wire = self._push_wire(s)
                    hdr = {"op": "push", "table": table, "lr": lr,
                           "wire": wire, "worker": self._push_ident,
                           "seq": seq}
                    if wire == "int4":
                        hdr["cols"] = int(g.shape[-1])
                    self._rpc(s, hdr,
                              [gids[gmask]] + quantize_rows(g[gmask], wire),
                              links=links)
                    return s, pmask, None
                wire = self._push_wire(s)
                payload = quantize_rows(g[gmask], wire) if gmask.any() \
                    else []
                hdr = {"op": "push_pull", "table": table, "lr": lr,
                       "wire": wire, "worker": self._push_ident,
                       "seq": seq, "n_push_bufs": len(payload)}
                if wire == "int4":
                    hdr["cols"] = int(g.shape[-1])
                reply, rows = self._rpc(
                    s, hdr,
                    [gids[gmask]] + payload + [pflat[pmask]],
                    links=links)
                return s, pmask, self._decode_pull(table, reply, rows)

        first_dim = None
        parts = list(self._pool.map(one, range(self.n)))
        for _, _, rows in parts:
            if rows is not None:
                first_dim = rows.shape[1]
                break
        if first_dim is None:
            first_dim = self.table_dim(table)
        out = np.empty((pflat.size, first_dim), np.float32)
        for _, mask, rows in parts:
            if rows is not None:
                out[mask] = rows
        return out.reshape(pull_ids.shape + (first_dim,))

    # -- liveness -----------------------------------------------------------
    def heartbeat(self):
        """Beat every endpoint, in parallel and WITHOUT retries: the next
        interval is the retry, and blocking retries on one dead endpoint
        would starve beats to the healthy servers — exactly the false
        lost-worker report the heartbeat exists to prevent.  A failing
        endpoint is skipped (and reported dead via _rpc's exhaustion
        path); the next successful beat revives it."""
        def one(s):
            try:
                self._rpc(s, {"op": "heartbeat",
                              "worker": self.worker_id}, retries=0)
            except (ConnectionError, OSError):
                pass
        list(self._pool.map(one, range(self.n)))

    def start_heartbeat(self, interval: float = 5.0):
        def loop():
            while not self._hb_stop.wait(interval):
                try:
                    self.heartbeat()
                except (RuntimeError, OSError):
                    pass
        self.heartbeat()
        self._hb_thread = threading.Thread(target=loop, daemon=True)
        self._hb_thread.start()

    # -- admin --------------------------------------------------------------
    def stat(self, server: int = 0):
        """Server stat reply (tables, workers, epoch, and — from a
        current-generation server — its measured transport counters),
        augmented with this client's own ``client_transport`` snapshot
        so one call surfaces both ends of the link."""
        reply, _ = self._rpc(server, {"op": "stat"})
        for name, t in reply.get("tables", {}).items():
            if t.get("dim"):
                self._dims[name] = t["dim"]
        reply["client_transport"] = self.transport.snapshot()
        return reply

    def transport_stats(self) -> dict:
        """Measured client-side transport counters: RPC count, wire
        bytes each way, per-op split, latency histograms."""
        return self.transport.snapshot()

    def sync_clock(self, server: int = 0) -> Optional[float]:
        """Estimate this process's clock offset to ``server`` over the
        ``hello`` handshake (NTP-style midpoint: ``server_time - (t0 +
        t1) / 2``) and install it on the tracer, so trace_merge can put
        every process's spans on the server's timeline.  Returns the
        offset in seconds, or None from an old server whose hello
        carries no time.

        The probe rides the RAW connection, single dial, bypassing the
        retry/death bookkeeping on purpose: it runs at client
        construction, when a co-launched server may simply not be
        listening yet, and a failed clock probe must not mark a healthy
        endpoint dead (mark_dead fires the elastic lost-peer channel
        and the later revival burns a flap)."""
        t0 = time.time()
        reply, _ = self._conns[server].rpc({"op": "hello", "wire": "f32"})
        t1 = time.time()
        if "time" not in reply:
            return None
        offset = float(reply["time"]) - (t0 + t1) / 2.0
        self.tracer.set_clock_offset(offset)
        return offset

    def set_epoch(self, epoch: int, fence_servers: bool = False,
                  n_workers: Optional[int] = None):
        """Adopt a membership epoch: every subsequent RPC is stamped with
        it.  ``fence_servers=True`` additionally installs the epoch on
        every server (elastic re-form), after which any client still
        stamping an older epoch — or none at all — gets its pushes
        rejected: the stale pre-epoch worker cannot corrupt the
        re-formed tables.  ``n_workers`` re-sizes the servers' bye
        quorum to the re-formed world."""
        self.epoch = int(epoch)
        if fence_servers:
            for s in range(self.n):
                self._rpc(s, {"op": "set_epoch", "epoch": self.epoch,
                              "n_workers": n_workers})

    def bye(self):
        self._hb_stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=2.0)
        for c in self._conns:
            try:
                # bye goes over the raw conn (no retries wanted on the
                # way out) so the epoch stamp _rpc would add must be
                # spelled out — a fenced server only counts current-epoch
                # byes toward its shutdown quorum
                header = {"op": "bye", "worker": self.worker_id}
                if self.epoch is not None:
                    header["epoch"] = self.epoch
                c.rpc(header)
            except (RuntimeError, OSError, ConnectionError):
                pass
            c.close()

    def shutdown_servers(self):
        for c in self._conns:
            try:
                c.rpc({"op": "shutdown"})
            except (RuntimeError, OSError, ConnectionError):
                pass


class RemoteEmbeddingTable:
    """pull/push-compatible stand-in for HostEmbeddingTable backed by a
    PsClient — DistributedEmbedding/AsyncCommunicator work unchanged on
    top (the lookup-table-op → pserver path of the reference)."""

    def __init__(self, client: PsClient, table: str, embedding_dim: int):
        self.client = client
        self.table = table
        self.embedding_dim = embedding_dim

    def pull(self, ids: np.ndarray) -> np.ndarray:
        return self.client.pull(self.table, ids)

    def push(self, ids: np.ndarray, grads: np.ndarray,
             lr: Optional[float] = None, seq: Optional[int] = None,
             links=None):
        self.client.push(self.table, ids, grads, lr=lr, seq=seq,
                         links=links)

    def push_pull(self, push_ids, push_grads, pull_ids,
                  lr: Optional[float] = None,
                  seq: Optional[int] = None, links=None) -> np.ndarray:
        """Coalesced push+pull in one RPC round-trip per shard — the
        hook PSTrainStep's prefetch pipeline rides (duck-typed: tables
        without it get a separate push then pull).  ``links`` stamp the
        deferred push's causal edges onto the carrying RPC span."""
        return self.client.push_pull(self.table, push_ids, push_grads,
                                     pull_ids, lr=lr, seq=seq,
                                     links=links)


# ---------------------------------------------------------------------------
# standalone entry (the role of the PS binary fleet.run_server launches)
# ---------------------------------------------------------------------------

def serve(port: int, table_specs: Sequence[str], host: str = "127.0.0.1",
          n_workers: Optional[int] = None, heartbeat_timeout: float = 30.0,
          announce=print):
    """table spec: name:rows:dim[:optimizer[:lr]]"""
    tables = {}
    for spec in table_specs:
        parts = spec.split(":")
        name, rows, dim = parts[0], int(parts[1]), int(parts[2])
        optim = parts[3] if len(parts) > 3 else "adagrad"
        lr = float(parts[4]) if len(parts) > 4 else 0.05
        tables[name] = HostEmbeddingTable(rows, dim, optim, lr)
    srv = PsServer(tables, host=host, port=port,
                   heartbeat_timeout=heartbeat_timeout, n_workers=n_workers)
    # push this shard's telemetry (incl. per-table request skew + hot
    # rows) to the cluster collector when the launcher exported an
    # endpoint; fire-and-forget — a dead collector costs nothing
    from paddle_tpu.framework import collector
    reporter = collector.auto_reporter(role="server",
                                       payload_extra=lambda: {
                                           "tables": srv.table_telemetry()})
    announce(f"PS_READY {srv.host}:{srv.port}", flush=True)
    try:
        srv.serve_forever()
    finally:
        if reporter is not None:
            reporter.stop(final_write=True)


# Spawn recipe for a server subprocess: the server is host-tier only
# (numpy tables + TCP) and must NOT contend for the accelerator the
# trainer holds (one process per chip) — so the platform override lands
# BEFORE any paddle_tpu import, and through jax.config so that it holds
# whatever JAX_PLATFORMS the child inherited.  Use:
#   subprocess.Popen([sys.executable, "-c", SERVER_BOOT, *args])
SERVER_BOOT = ("import jax, sys; "
               "jax.config.update('jax_platforms', 'cpu'); "
               "from paddle_tpu.distributed.ps.service import _main; "
               "sys.exit(_main())")


def _main():
    ap = argparse.ArgumentParser(description="paddle_tpu PS shard server")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--table", action="append", required=True,
                    help="name:rows:dim[:optimizer[:lr]]")
    ap.add_argument("--n-workers", type=int, default=None,
                    help="shut down after this many workers say bye")
    ap.add_argument("--heartbeat-timeout", type=float, default=30.0)
    a = ap.parse_args()
    serve(a.port, a.table, a.host, a.n_workers, a.heartbeat_timeout)


if __name__ == "__main__":
    _main()
