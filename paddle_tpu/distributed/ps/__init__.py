"""Parameter-server capability, TPU-native.

The reference ships two PS generations (SURVEY.md §2.4): brpc servers with
sparse tables (paddle/fluid/distributed/service/brpc_ps_server.cc, tables in
distributed/table/common_sparse_table.cc), async communicators
(service/communicator.cc) and GEO-SGD delta sync, plus GPU-resident hash
tables (framework/fleet/heter_ps/).  Capability = embeddings far larger
than one device, updated sparsely, with async/geo consistency modes.

TPU-native mapping, three tiers:

- **Device tier — ``ShardedEmbedding``**: the table lives in HBM sharded
  over a mesh axis (rows split).  XLA partitions the gather and the
  scatter-add gradient; this is the SparseCore-style path and replaces the
  GPU heter-PS (hashtable.h) for tables that fit the slice.
- **Device exchange tier — ``MeshShardedEmbedding`` (device_table.py)**:
  range-sharded table + explicit per-step dedup / all-gather id exchange /
  psum_scatter row return — the heter_ps pull_sparse/push_sparse cycle
  (heter_comm.h) as XLA collectives, for tables that fit aggregate HBM
  but not one chip.
- **Host tier — ``HostEmbeddingTable`` + ``DistributedEmbedding``**: the
  table lives in host RAM (numpy, trillion-scale capable), rows are pulled
  per batch to the device and gradient rows pushed back into a host-side
  optimizer — the role of PullSparseVarsSync/PushSparseVarsAsync
  (framework/fleet/fleet_wrapper.h:111).  ``AsyncCommunicator`` batches
  pushes on a worker thread (service/communicator.cc semantics), and
  ``geo`` mode accumulates deltas and folds them in every k steps
  (sparse_geo_table.cc semantics).  The multi-host transport lives in
  ps/service.py (TCP pull/push + heartbeat); ``HashEmbeddingTable`` adds
  the dynamic-vocab hash-table generation, and ps/graph.py the GNN
  sampling service on the same transport.
"""
from __future__ import annotations

import queue
import threading
from collections import deque
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import Parameter, Tensor, apply1
from paddle_tpu.framework import health, locks, monitor
from paddle_tpu.jit import not_to_static
from paddle_tpu.distributed.ps.device_table import (
    DeviceEmbeddingTrainStep, HotRowSketch, MeshShardedEmbedding,
    mesh_sharded_lookup)
from paddle_tpu.nn.layer.layers import Layer
from paddle_tpu.parallel.mesh import DistAttr

__all__ = ["HashEmbeddingTable", "MeshShardedEmbedding",
           "DeviceEmbeddingTrainStep", "HotRowSketch",
           "ShardedEmbedding", "HostEmbeddingTable", "DistributedEmbedding",
           "AsyncCommunicator", "PSTrainStep", "mesh_sharded_lookup"]


class ShardedEmbedding(Layer):
    """Embedding with rows sharded over a mesh axis (device tier).

    Unlike VocabParallelEmbedding (tp_layers.py, activation-parallel), this
    is the *capacity* path: use axis "mp" (or a dedicated axis) purely to
    fit a big table; gather/scatter stay XLA-partitioned."""

    def __init__(self, num_embeddings: int, embedding_dim: int,
                 mesh_axis: str = "mp", sparse: bool = True,
                 weight_attr=None, name=None, scale_grad_by_freq=False):
        super().__init__()
        self._num_embeddings = num_embeddings
        self._embedding_dim = embedding_dim
        std = 1.0 / max(1.0, embedding_dim ** 0.5)
        init = np.random.default_rng(0).uniform(
            -std, std, size=(num_embeddings, embedding_dim)).astype(
                np.float32)
        self.weight = Parameter(init, name=name or "sharded_embedding")
        self.weight.dist_attr = DistAttr((mesh_axis, None))

    def forward(self, x):
        return apply1(lambda w, ids: w[ids], self.weight, x,
                      name="sharded_embedding")


class HostEmbeddingTable:
    """Host-RAM sparse table with optimizer-on-push (host tier).

    Parity: distributed/table/common_sparse_table.cc — rows created on
    first touch, per-row optimizer state, save/load.  Supported optimizers:
    'sgd', 'adagrad' (the reference's common choices for sparse slots)."""

    def __init__(self, num_embeddings: int, embedding_dim: int,
                 optimizer: str = "adagrad", learning_rate: float = 0.05,
                 initializer_range: float = 0.05, seed: int = 0):
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.optimizer = optimizer
        self.learning_rate = learning_rate
        rng = np.random.default_rng(seed)
        # float32-native generation with in-place scaling: uniform() would
        # materialise a float64 intermediate and the non-inplace arithmetic
        # three more full-size temporaries — at PS scale (100M rows × 65 =
        # 26 GB) that is ~4× the RAM the reference's C++ tables use
        t = rng.random((num_embeddings, embedding_dim), dtype=np.float32)
        t *= np.float32(2.0 * initializer_range)
        t -= np.float32(initializer_range)
        self._table = t
        if optimizer == "adagrad":
            self._g2 = np.zeros((num_embeddings,), np.float32)
        elif optimizer != "sgd":
            raise ValueError(f"unsupported table optimizer {optimizer!r}")
        self._lock = locks.lock("ps.host_table")
        # bounded hot-row telemetry (FLAGS_ps_hot_row_k; 0 = off): which
        # rows this table actually serves — the signal a serving-side
        # row cache / the cluster collector's hot-table view consumes
        from paddle_tpu.framework.flags import flag
        k = int(flag("ps_hot_row_k"))
        self.hot_rows = HotRowSketch(k) if k > 0 else None

    def pull(self, ids: np.ndarray) -> np.ndarray:
        """PullSparse (fleet_wrapper.h:111): rows for this batch."""
        if self.hot_rows is not None:
            self.hot_rows.update(ids)
        with self._lock:
            return self._table[ids]

    def push(self, ids: np.ndarray, grads: np.ndarray,
             lr: Optional[float] = None):
        """PushSparse: apply row gradients with the table optimizer.
        Duplicate ids within a batch are accumulated first (the
        GradientAccumulator's SelectedRows merge-add)."""
        lr = self.learning_rate if lr is None else lr
        flat_ids = ids.reshape(-1)
        flat_g = grads.reshape(-1, self.embedding_dim)
        uniq, inv = np.unique(flat_ids, return_inverse=True)
        acc = np.zeros((len(uniq), self.embedding_dim), np.float32)
        np.add.at(acc, inv, flat_g)
        with self._lock:
            if self.optimizer == "adagrad":
                self._g2[uniq] += (acc ** 2).mean(axis=1)
                denom = np.sqrt(self._g2[uniq])[:, None] + 1e-6
                self._table[uniq] -= lr * acc / denom
            else:
                self._table[uniq] -= lr * acc

    # save/load (reference: common_sparse_table save/load)
    def state_dict(self) -> Dict[str, np.ndarray]:
        d = {"table": self._table, "optimizer": self.optimizer}
        if self.optimizer == "adagrad":
            d["g2"] = self._g2
        return d

    def set_state_dict(self, d):
        self._table = np.asarray(d["table"], np.float32)
        if self.optimizer == "adagrad" and "g2" in d:
            self._g2 = np.asarray(d["g2"], np.float32)


class AsyncCommunicator:
    """Async push batching (parity: distributed/service/communicator.cc —
    send queues + merge threads).  mode='async' applies pushes on a worker
    thread; mode='geo' accumulates deltas and folds every k_steps (GEO-SGD,
    sparse_geo_table.cc)."""

    def __init__(self, table: HostEmbeddingTable, mode: str = "async",
                 k_steps: int = 4, send_queue_size: int = 16):
        assert mode in ("async", "geo", "sync")
        self.table = table
        self.mode = mode
        self.k_steps = k_steps
        self._q: "queue.Queue" = queue.Queue(maxsize=send_queue_size)
        self._geo_acc: Dict[int, np.ndarray] = {}
        self._geo_count = 0
        self._stop = threading.Event()
        self._thread = None
        if mode == "async":
            # the thread holds only WEAK references to the communicator
            # and its table: a live thread target with a strong ref would
            # pin the (tens of GB) host table forever after the embedding
            # is dropped — the worker exits on its own once the
            # communicator is collected (or stop() is called).  The table
            # weakref is separate so that when the communicator dies but
            # the table is still alive elsewhere, queued pushes DRAIN
            # into it instead of being dropped (see push()).
            import weakref
            self._thread = threading.Thread(
                target=AsyncCommunicator._worker_loop,
                args=(weakref.ref(self), weakref.ref(table)), daemon=True)
            self._thread.start()

    @staticmethod
    def _drain_queue(q: "queue.Queue", table):
        """Apply every still-queued push to ``table`` (no-op when the
        table is gone too) — the communicator-collected exit path, so
        queued gradients land instead of being silently dropped whenever
        the table is independently alive."""
        while table is not None:
            try:
                ids, grads = q.get_nowait()
            except queue.Empty:
                return
            try:
                table.push(ids, grads)
            finally:
                q.task_done()

    @staticmethod
    def _worker_loop(comm_ref, table_ref):
        comm = comm_ref()
        if comm is None:
            return
        # q/stop are plain attributes — holding them pins neither the
        # communicator nor the table
        q, stop = comm._q, comm._stop
        del comm
        while True:
            if stop.is_set():
                return
            try:
                ids, grads = q.get(timeout=0.05)
            except queue.Empty:
                if comm_ref() is None:
                    AsyncCommunicator._drain_queue(q, table_ref())
                    return
                continue
            comm = comm_ref()
            if comm is None:
                table = table_ref()
                try:
                    if table is not None:
                        table.push(ids, grads)
                finally:
                    q.task_done()
                AsyncCommunicator._drain_queue(q, table)
                return
            try:
                comm.table.push(ids, grads)
            finally:
                # a push that exhausts retries must still account the
                # queue item, or flush()/stop() (q.join()) hang forever
                q.task_done()
            del comm                 # don't pin the table across the wait

    def push(self, ids: np.ndarray, grads: np.ndarray):
        """Queue (async), accumulate (geo) or apply (sync) a gradient push.

        Flush-before-drop contract: async-mode pushes are applied by a
        worker thread holding only weak references.  If the communicator
        is garbage-collected with pushes still queued, the worker drains
        them into the table only when the table is independently alive;
        when communicator and table die together (the common
        DistributedEmbedding case) queued pushes are dropped.  Call
        ``flush()`` (or ``stop()``) before releasing the last reference
        whenever every queued gradient must land."""
        if self.mode == "sync":
            self.table.push(ids, grads)
        elif self.mode == "async":
            self._q.put((ids, grads))
        else:  # geo: accumulate deltas, fold every k steps
            flat_ids = ids.reshape(-1)
            flat_g = grads.reshape(-1, self.table.embedding_dim)
            for i, g in zip(flat_ids.tolist(), flat_g):
                if i in self._geo_acc:
                    self._geo_acc[i] = self._geo_acc[i] + g
                else:
                    self._geo_acc[i] = g.copy()
            self._geo_count += 1
            if self._geo_count >= self.k_steps:
                self.flush()

    def flush(self):
        if self.mode == "async":
            self._q.join()
        elif self.mode == "geo" and self._geo_acc:
            ids = np.asarray(list(self._geo_acc), np.int64)
            grads = np.stack(list(self._geo_acc.values()))
            self.table.push(ids, grads)
            self._geo_acc.clear()
            self._geo_count = 0

    def stop(self):
        self.flush()
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=1.0)


class DistributedEmbedding(Layer):
    """Layer over a HostEmbeddingTable: forward pulls rows, backward pushes
    gradient rows through the communicator (parity: the lookup-table op +
    DownpourWorker pull/push cycle, device_worker.h:271)."""

    def __init__(self, num_embeddings: int, embedding_dim: int,
                 optimizer: str = "adagrad", learning_rate: float = 0.05,
                 mode: str = "sync", k_steps: int = 4, seed: int = 0,
                 table=None):
        super().__init__()
        # ``table`` may be a RemoteEmbeddingTable (ps.service) — then pulls
        # and pushes travel the multi-host PS transport instead of local RAM
        self.table = table if table is not None else HostEmbeddingTable(
            num_embeddings, embedding_dim, optimizer, learning_rate,
            seed=seed)
        self.communicator = AsyncCommunicator(self.table, mode=mode,
                                              k_steps=k_steps)
        self._embedding_dim = embedding_dim

    @not_to_static
    def forward(self, x):
        # host tier by contract: ids leave the device, rows come back
        # from host RAM / the PS transport — never trace this forward
        # (the @not_to_static marker is honored by dy2static AND the
        # jit-safety linter, which would otherwise flag the numpy calls)
        ids = np.asarray(x.numpy() if isinstance(x, Tensor) else x,
                         np.int64)
        rows = self.table.pull(ids)                   # host gather
        out = Tensor(jnp.asarray(rows), stop_gradient=False)
        out.is_leaf_ = True

        comm = self.communicator

        def push_hook(grad: Tensor):
            comm.push(ids, np.asarray(grad.numpy(), np.float32))
            return grad

        out.register_hook(push_hook)
        return out

    def flush(self):
        self.communicator.flush()


class HashEmbeddingTable:
    """Dynamic-vocab sparse table: rows exist only once touched.

    Parity: the hash-table PS generation — framework/fleet/heter_ps/
    hashtable.h + distributed/table/common_sparse_table.cc's
    first-touch row creation — behind the reference's "trillions of
    parameters" claim: the id space is unbounded (feature hashes), and
    memory grows with *touched* rows, not vocabulary size.

    Same pull/push surface as HostEmbeddingTable, so DistributedEmbedding,
    AsyncCommunicator, and the PS service transport all work unchanged;
    ids may be any int64 (hash values included).
    """

    def __init__(self, embedding_dim: int, optimizer: str = "adagrad",
                 learning_rate: float = 0.05,
                 initializer_range: float = 0.05, seed: int = 0):
        self.num_embeddings = 0            # dynamic; grows on touch
        self.embedding_dim = embedding_dim
        self.optimizer = optimizer
        self.learning_rate = learning_rate
        self._init_range = initializer_range
        self._seed = seed
        if optimizer not in ("adagrad", "sgd"):
            raise ValueError(f"unsupported table optimizer {optimizer!r}")
        self._rows: Dict[int, np.ndarray] = {}
        self._g2: Dict[int, float] = {}
        self._lock = locks.lock("ps.dynamic_table")
        from paddle_tpu.framework.flags import flag
        k = int(flag("ps_hot_row_k"))
        self.hot_rows = HotRowSketch(k) if k > 0 else None

    def _row(self, i: int) -> np.ndarray:
        r = self._rows.get(i)
        if r is None:
            # deterministic per-id init: same id hashes to the same row on
            # any shard/restart (common_sparse_table's initializer role)
            rng = np.random.default_rng((self._seed * 0x9E3779B9 + i)
                                        & 0xFFFFFFFF)
            r = rng.uniform(-self._init_range, self._init_range,
                            self.embedding_dim).astype(np.float32)
            self._rows[i] = r
            self.num_embeddings = len(self._rows)
        return r

    def pull(self, ids: np.ndarray) -> np.ndarray:
        ids = np.asarray(ids, np.int64)
        flat = ids.reshape(-1)
        if self.hot_rows is not None:
            self.hot_rows.update(flat)
        with self._lock:
            out = np.stack([self._row(int(i)) for i in flat])
        return out.reshape(ids.shape + (self.embedding_dim,))

    def push(self, ids: np.ndarray, grads: np.ndarray, lr=None):
        lr = self.learning_rate if lr is None else lr
        flat = np.asarray(ids, np.int64).reshape(-1)
        g = np.asarray(grads, np.float32).reshape(flat.size,
                                                  self.embedding_dim)
        uniq, inv = np.unique(flat, return_inverse=True)
        acc = np.zeros((uniq.size, self.embedding_dim), np.float32)
        np.add.at(acc, inv, g)
        with self._lock:
            for k, i in enumerate(uniq.tolist()):
                row = self._row(i)
                if self.optimizer == "adagrad":
                    self._g2[i] = self._g2.get(i, 0.0) + float(
                        (acc[k] ** 2).mean())
                    row -= lr * acc[k] / (np.sqrt(self._g2[i]) + 1e-6)
                else:
                    row -= lr * acc[k]

    # save/load: ids + rows arrays (ordered), g2 aligned
    def state_dict(self):
        with self._lock:
            ids = np.fromiter(self._rows.keys(), np.int64,
                              count=len(self._rows))
            table = (np.stack([self._rows[int(i)] for i in ids])
                     if ids.size else
                     np.zeros((0, self.embedding_dim), np.float32))
            d = {"ids": ids, "table": table, "optimizer": self.optimizer}
            if self.optimizer == "adagrad":
                d["g2"] = np.asarray([self._g2.get(int(i), 0.0)
                                      for i in ids], np.float32)
            return d

    def set_state_dict(self, d):
        with self._lock:
            self._rows = {int(i): np.asarray(r, np.float32)
                          for i, r in zip(d["ids"], d["table"])}
            if "g2" in d:
                self._g2 = {int(i): float(v)
                            for i, v in zip(d["ids"], d["g2"])}
            self.num_embeddings = len(self._rows)


class PSTrainStep:
    """The DownpourWorker per-batch cycle as one fused device computation.

    Parity: the reference's PS training loop (device_worker.h:271
    DownpourWorker::TrainFiles — FillSparseValue pull, net forward/
    backward, PushSparse gradients), where the net runs op-by-op on GPU
    and pull/push are brpc RPCs.  TPU-native restructuring: the whole
    dense net — forward, backward, dense-optimizer update, AND the
    gradient w.r.t. the pulled embedding rows — is ONE jitted XLA
    computation; the sparse table stays in host RAM (HostEmbeddingTable /
    RemoteEmbeddingTable over the PS TCP transport) and pushes ride the
    AsyncCommunicator worker thread, overlapping the next device step.

    ``loss_fn(model, rows, *inputs) -> scalar`` — ``rows`` is the pulled
    (B, F, dim) embedding Tensor (a differentiated leaf).

    Host↔device traffic is minimised the way a real PS worker does
    (fleet_wrapper merges duplicate keys before pull/push): only UNIQUE
    ids are pulled, the per-slot rows are re-gathered on device (whose
    gather-VJP accumulates duplicate-id gradients for free, replacing
    the host's np.add.at), and the wire dtype is bfloat16 by default —
    together ~8× fewer bytes than naive per-slot f32 rows on skewed id
    distributions.  Unique counts are bucketed (next power of two) so
    the XLA signature cache stays small.

    **Pull/compute overlap** — announce the NEXT batch's ids with
    :meth:`prefetch` and the blocking pull disappears behind the chip::

        step.prefetch(ids[0])
        for n in range(N):
            if n + 1 < N:
                step.prefetch(ids[n + 1])
            loss = step(ids[n], x[n], y[n])
        step.flush()

    Each step then runs: consume the prefetched rows (already pulled
    while the PREVIOUS step's device computation ran), dispatch the
    fused XLA step, and — right after dispatch, while the chip is busy
    — issue the announced next batch's fan-out on a background
    executor, coalescing the previous step's deferred gradient push
    into the same per-shard RPC (``push_pull``: one round-trip per
    shard per step, the DownpourWorker amortization).  Ordering /
    staleness guarantee: the rows pulled for step N+1 reflect every
    push up to step N-1 — one step more staleness than the async
    communicator path, none at all vs. the geo path.  A membership
    re-form (``elastic.reform``) between issue and consume is detected
    by the epoch stamp: the stale prefetched rows are discarded and
    re-pulled under the new epoch, a coalesced push that the fence
    rejected stays dropped (the re-form restored past it), and any
    other prefetch failure replays the push through the synchronous
    path — the server's ``(worker, seq)`` dedup absorbs the replay if
    the original actually landed.  The ``ps.pipeline`` chaos point
    fires at the head of every background task so the chaos suite can
    prove all of this on demand.  ``prefetch_depth``
    (FLAGS_ps_prefetch_depth) bounds the in-flight prefetches; 0
    disables the pipeline (prefetch() becomes a no-op), 1 is the
    classic double buffer.
    """

    def __init__(self, model: Layer, loss_fn, optimizer,
                 embedding: "DistributedEmbedding", donate: bool = True,
                 transfer_dtype="bfloat16",
                 prefetch_depth: Optional[int] = None):
        from paddle_tpu.framework.flags import flag
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.embedding = embedding
        self.donate = donate
        self.transfer_dtype = str(transfer_dtype)
        self.prefetch_depth = int(flag("ps_prefetch_depth")) \
            if prefetch_depth is None else int(prefetch_depth)
        self._opt_states = None
        self._cache: Dict[tuple, object] = {}
        self._last_call_start = None
        # -- prefetch pipeline state (single training thread drives it;
        # only the executor tasks run concurrently, and they touch only
        # thread-safe table/client objects + local arrays)
        self._announced: "deque" = deque()   # ids awaiting issue
        self._inflight: "deque" = deque()    # issued background tasks
        # deferred (uniq_ids, grads) pushes awaiting coalesce — a QUEUE,
        # not a slot: when a step has nothing left to issue (the last
        # step of an epoch, a fault-degraded stretch) the previous
        # step's deferred push is still here when this step stashes its
        # own, and a single slot would silently drop a gradient
        self._pending_push: list = []
        self._prefetch_pool = None           # lazy ThreadPoolExecutor

    def _tracer(self):
        """The tracer this step's spans go to: the PS client's (so step
        and RPC spans share one file/label) or the process default."""
        from paddle_tpu.framework import observability
        client = getattr(self.embedding.table, "client", None)
        t = getattr(client, "tracer", None)
        return t if t is not None else observability.tracer

    @staticmethod
    def _end_prefetch_span(inf, status, **attrs):
        sp = inf.get("span")
        if sp is not None:
            sp.end(status=status, **attrs)

    # -- prefetch pipeline --------------------------------------------------
    @staticmethod
    def _unique_prep(ids_np):
        """Unique ids + inverse map + power-of-two padded id vector (the
        signature-cache bucketing) — the host-side prep every pull
        needs; runs on the background executor when pipelined."""
        import numpy as _np
        uniq, inv = _np.unique(ids_np.reshape(-1), return_inverse=True)
        cap = max(256, 1 << int(_np.ceil(_np.log2(len(uniq)))))
        uniq_p = _np.zeros((cap,), _np.int64)
        uniq_p[:len(uniq)] = uniq
        return uniq, inv, uniq_p

    def prefetch(self, ids):
        """Announce the ids of an upcoming batch.  The actual shard
        fan-out is issued right after the *current* step's device
        dispatch (see class docstring), so the pull hides behind the
        chip.  No-op when the pipeline is disabled
        (``prefetch_depth=0``)."""
        if self.prefetch_depth <= 0:
            return
        import numpy as _np
        self._announced.append(_np.asarray(
            ids.numpy() if isinstance(ids, Tensor) else ids, _np.int64))

    @staticmethod
    def _push_links(push):
        """The causal edges a coalesced deferred push stamps onto the
        RPC span that carries it (``PsClient._rpc links=``): one
        ``deferred_push`` link per producing train.step span.  The
        rendered edge says "this RPC carries step N's gradient", so
        blame can tie a slow coalesced round-trip back to the step
        that deferred into it.  None when nothing to link (local
        tables, tracing off)."""
        if push is None or len(push) < 4 or not push[3]:
            return None
        return [{"span": sid, "kind": "deferred_push"}
                for sid in push[3]]

    def _prefetch_task(self, table, ids_np, push, span=None):
        """Background fan-out: unique the announced ids and run the
        coalesced push+pull round-trip (plain pull when no push is
        pending or the table has no coalesced op).  Runs under the
        prefetch span opened at issue time, so its RPCs parent to it."""
        import time as _time

        from paddle_tpu.framework import chaos
        ctx = span.context() if span is not None else None
        with self._tracer().activate(ctx):
            chaos.fault_point("ps.pipeline",  # pta: disable=PTA301 (PSTrainStep._consume_prefetch owns fallback: sync re-pull + push replay)
                              meta={"n_ids": int(ids_np.size),
                                    "coalesced_push": push is not None})
            uniq, inv, uniq_p = self._unique_prep(ids_np)
            if push is not None and hasattr(table, "push_pull"):
                rows = table.push_pull(push[0], push[1], uniq_p,
                                       seq=push[2],
                                       links=self._push_links(push))
            else:
                if push is not None:
                    self._replay_push(push)
                rows = table.pull(uniq_p)
            if span is not None:
                # when the background work actually FINISHED (epoch us)
                # — the span itself stays open until the consuming step
                # settles it, so blame needs this to tell a hidden pull
                # (done before the step began) from a blocking one
                span.set_attr("done_ts", _time.time() * 1e6)
            return uniq, inv, uniq_p, rows

    def _take_pending_push(self):
        """Drain the deferred-push queue into one ``(ids, grads, seq,
        producer_span_ids)`` payload.  Usually 0 or 1 entries; multiple
        (fault-degraded stretches) concatenate — the table's
        duplicate-id merge accumulates them exactly like separate
        pushes under sgd, and within one batch-merge granularity under
        adagrad.  The dedup ``seq`` is allocated HERE, once per
        payload, so a replay after a failed/ambiguous first attempt
        re-sends the SAME stamp and the server's dedup can actually
        absorb it.  ``producer_span_ids`` are the train.step spans that
        deferred each gradient — linked onto the carrying RPC span as
        ``deferred_push`` causal edges."""
        import numpy as _np
        if not self._pending_push:
            return None
        if len(self._pending_push) == 1:
            ids_p, g_p = self._pending_push[0][:2]
        else:
            ids_p = _np.concatenate([p[0] for p in self._pending_push])
            g_p = _np.concatenate([p[1] for p in self._pending_push])
        sids = [p[2] for p in self._pending_push
                if len(p) > 2 and p[2] is not None]
        self._pending_push.clear()
        client = getattr(self.embedding.table, "client", None)
        seq = client._next_seq() if client is not None else None
        return (ids_p, g_p, seq, sids)

    def _replay_push(self, push):
        """Re-send a coalesced push whose first attempt failed or whose
        outcome is unknown, reusing its original seq stamp so the
        server drops the copy if the first attempt actually landed."""
        table = self.embedding.table
        client = getattr(table, "client", None)
        if client is not None and push[2] is not None:
            table.push(push[0], push[1], seq=push[2],
                       links=self._push_links(push))
        else:
            table.push(push[0], push[1])

    def _issue_prefetch(self):
        """Issue announced fan-outs (up to ``prefetch_depth`` in
        flight) onto the background executor, coalescing the previous
        step's deferred gradient push into the first one."""
        while (self.prefetch_depth > 0 and self._announced
               and len(self._inflight) < self.prefetch_depth):
            ids_np = self._announced.popleft()
            push = self._take_pending_push()
            table = self.embedding.table
            client = getattr(table, "client", None)
            if self._prefetch_pool is None:  # pta: disable=PTA404 (train-loop thread only: prefetch issue/consume both run on the consumer thread; the pool exists before any task can race it)
                from concurrent.futures import ThreadPoolExecutor
                self._prefetch_pool = ThreadPoolExecutor(
                    max_workers=max(1, self.prefetch_depth),
                    thread_name_prefix="ps-prefetch")
            # the span covers the whole in-flight window (issue →
            # settle/consume), ending with the prefetch's real fate:
            # "ok", or "error" with the reason (task failure, reorder,
            # reform-staleness)
            span = self._tracer().start_span(
                "ps.prefetch", detached=True,
                attrs={"n_ids": int(ids_np.size),
                       "coalesced_push": push is not None})
            span = span if span.span_id is not None else None
            self._inflight.append({
                "key": ids_np, "push": push, "span": span,
                "epoch": getattr(client, "epoch", None),
                "future": self._prefetch_pool.submit(
                    self._prefetch_task, table, ids_np, push, span)})

    def _settle_inflight(self, inf):
        """Resolve one in-flight prefetch, owning the error policy for
        its coalesced push.  Returns the task result, or ``None`` when
        the task failed (after replaying the push where the contract
        requires it):

        * elastic-fence rejection (``stale membership epoch``) — the
          push stays DROPPED: the re-form restored past it;
        * any other server-side error — replay the push synchronously
          (a genuine table fault then re-raises from the replay
          instead of vanishing);
        * transport failure (injected ``ps.pipeline``/``ps.rpc`` fault,
          retries exhausted) — replay; the server's (worker, seq)
          stamp reservation absorbs the replay if the original landed.

        Replays go through :meth:`_replay_push`, which re-sends the
        payload's ORIGINAL seq — a fresh stamp would defeat the dedup
        exactly when it matters (push half applied, pull half failed).
        """
        try:
            return inf["future"].result()
        except RuntimeError as e:
            self._end_prefetch_span(inf, "error", reason="server_error",
                                    exc=repr(e))
            if inf["push"] is not None and \
                    "stale membership epoch" not in str(e):
                self._replay_push(inf["push"])
            return None
        except (ConnectionError, OSError) as e:
            self._end_prefetch_span(inf, "error", reason="transport",
                                    exc=repr(e))
            if inf["push"] is not None:
                self._replay_push(inf["push"])
            return None

    @staticmethod
    def _link_prefetch(inf, step_span, kind):
        """Record the causal edge from a prefetch span to the step that
        consumed (or fell back past) it: ``kind="prefetch"`` — the rows
        arrived through the pipeline; ``kind="sync_fallback"`` — the
        prefetch failed/was stale and the step re-pulled synchronously,
        so the time burned waiting on the doomed task still attributes
        to ``ps_wait`` in the blame vector instead of vanishing into
        ``other``."""
        sp = inf.get("span")
        if sp is not None and step_span is not None:
            step_span.link(sp.span_id, kind)

    def _consume_prefetch(self, ids_np, step_span=None):
        """Take the head in-flight prefetch for this batch; ``None``
        means "pull synchronously" (nothing prefetched, the prefetch
        failed, or a membership re-form made its rows stale).  The
        consuming ``train.step`` span records the causal link either
        way (``prefetch`` on a hit, ``sync_fallback`` on a miss)."""
        import numpy as _np
        if not self._inflight:
            # the head announcement may be THIS batch's own (the
            # warm-up call before the first step): drop it, or the
            # issue stage would re-pull a batch already pulled here
            if self._announced and _np.array_equal(self._announced[0],
                                                   ids_np):
                self._announced.popleft()
            return None
        inf = self._inflight.popleft()
        client = getattr(self.embedding.table, "client", None)
        got = self._settle_inflight(inf)
        if got is None:            # failed: span ended by the settle path
            self._link_prefetch(inf, step_span, "sync_fallback")
            monitor.stat_add("ps_prefetch_misses_total")
            health.observe("ps_prefetch_miss", 1.0)
            return None
        if not _np.array_equal(inf["key"], ids_np):
            # stream reordered: rows are another batch's
            self._end_prefetch_span(inf, "error", reason="reordered")
            self._link_prefetch(inf, step_span, "sync_fallback")
            monitor.stat_add("ps_prefetch_misses_total")
            health.observe("ps_prefetch_miss", 1.0)
            return None
        if client is not None and inf["epoch"] != client.epoch:
            # re-formed mid-flight: rows are stale, discard them
            self._end_prefetch_span(inf, "error", reason="stale_epoch",
                                    issued_epoch=inf["epoch"],
                                    epoch=client.epoch)
            self._link_prefetch(inf, step_span, "sync_fallback")
            monitor.stat_add("ps_prefetch_misses_total")
            health.observe("ps_prefetch_miss", 1.0)
            return None
        self._end_prefetch_span(inf, "ok")
        self._link_prefetch(inf, step_span, "prefetch")
        monitor.stat_add("ps_prefetch_hits_total")
        health.observe("ps_prefetch_miss", 0.0)
        return got

    def _make_step(self, ids_shape, numerics_aux: bool = False):
        model, loss_fn, opt = self.model, self.loss_fn, self.optimizer

        def step(params, opt_states, buffers, key, lr, rows_u, inv,
                 *inputs):
            from paddle_tpu.jit import (apply_functional_update,
                                        functional_loss_call)

            def lf(p, ru):
                # the pulled unique rows re-gathered per slot on device;
                # the gather VJP sums duplicate-id grads for free
                rows = ru.astype(jnp.float32)[inv].reshape(
                    tuple(ids_shape) + (ru.shape[-1],))
                return functional_loss_call(
                    model, loss_fn, p, buffers, key, inputs,
                    lead_tensors=(Tensor(rows),))

            (loss, new_buffers), (grads, drows_u) = jax.value_and_grad(
                lf, argnums=(0, 1), has_aux=True)(params, rows_u)
            new_params, new_states = apply_functional_update(
                opt, grads, params, opt_states, lr)
            if numerics_aux:
                from paddle_tpu.framework import numerics
                # the pulled-row gradient is a first-class leaf of the
                # numerics view ("embedding.rows"): a NaN entering
                # through the sparse tier attributes there, not to a
                # dense leaf.  Its update happens host-side on the PS,
                # so its update term is an exact zero
                g2 = dict(grads)
                g2["embedding.rows"] = drows_u
                p2 = dict(params)
                p2["embedding.rows"] = rows_u
                np2 = dict(new_params)
                np2["embedding.rows"] = rows_u
                aux = numerics.compute_aux(g2, p2, np2, loss)
                return (new_params, new_states, new_buffers, loss,
                        drows_u, aux)
            return new_params, new_states, new_buffers, loss, drows_u

        donate = (0, 1) if self.donate else ()
        return jax.jit(step, donate_argnums=donate)

    def __call__(self, ids, *inputs):
        import time as _time
        # postmortem ring: the pulled-row ids ARE the sparse tier's step
        # input — ring them with the dense batch so a PS incident
        # replays the exact rows it pulled (one flag lookup disarmed)
        from paddle_tpu.framework import incident
        incident.maybe_note(self, (ids,) + tuple(inputs))
        t_start = _time.perf_counter()
        step_span = self._tracer().start_span(
            "train.step",
            attrs={"step": int(getattr(self.optimizer,
                                       "_global_step", 0))})
        with step_span:
            loss = self._call_inner(ids, step_span, *inputs)
        # a step: from one call's start to the next's (jit.TrainStep)
        before, self._last_call_start = self._last_call_start, t_start
        if before is not None:
            step_ms = (t_start - before) * 1e3
            monitor.observe("train_step_ms", step_ms)
            health.observe("train_step_ms", step_ms)
        monitor.stat_add("train_steps_total")
        return loss

    def _call_inner(self, ids, step_span, *inputs):
        import numpy as _np
        import ml_dtypes
        ids_np = _np.asarray(
            ids.numpy() if isinstance(ids, Tensor) else ids, _np.int64)
        got = self._consume_prefetch(ids_np, step_span)
        pipelined = got is not None
        if got is None:
            # synchronous path (no/failed prefetch): still coalesce a
            # deferred push into the pull's round-trip when the table
            # supports it, so the degraded pipeline keeps one RPC/step
            uniq, inv, uniq_p = self._unique_prep(ids_np)
            push = self._take_pending_push()
            table = self.embedding.table
            if push is not None and hasattr(table, "push_pull"):
                rows_u = table.push_pull(push[0], push[1], uniq_p,
                                         seq=push[2],
                                         links=self._push_links(push))
            else:
                if push is not None:
                    self._replay_push(push)
                rows_u = table.pull(uniq_p)               # host gather
        else:
            uniq, inv, uniq_p, rows_u = got
        if self.transfer_dtype in ("bfloat16", "bf16"):
            rows_u = rows_u.astype(ml_dtypes.bfloat16)

        model = self.model
        params = {n: p._data for n, p in model.named_parameters()}
        buffers = {n: b._data for n, b in model.named_buffers()
                   if b is not None}
        if self._opt_states is None:  # pta: disable=PTA404 (train-loop thread only: step() is driven by the single consumer thread; prefetch tasks never touch optimizer state)
            self._opt_states = self.optimizer.functional_init_states(params)
        arrs = [i._data if isinstance(i, Tensor) else jnp.asarray(i)
                for i in inputs]
        from paddle_tpu.framework import numerics
        armed = numerics.enabled()
        # marker only when armed: the disarmed signature (and jaxpr)
        # stays byte-identical to the plane-less seed
        sig = (rows_u.shape, str(rows_u.dtype), ids_np.shape,
               tuple((a.shape, str(a.dtype)) for a in arrs)) \
            + (("numerics",) if armed else ())
        fn = self._cache.get(sig)
        compile_cause = None
        if fn is None:
            compile_cause = health.classify_recompile(
                sig, list(self._cache))
            fn = self._cache[sig] = self._make_step(
                ids_np.shape, numerics_aux=armed)
        else:
            health.note_cache_hit("PSTrainStep")
        from paddle_tpu.tensor.random import default_generator
        key = default_generator.split()
        lr = jnp.float32(self.optimizer.get_lr())
        with health.timed_compile("PSTrainStep", compile_cause):
            out = fn(
                params, self._opt_states, buffers, key, lr,
                jnp.asarray(rows_u), jnp.asarray(inv.astype(_np.int32)),
                *arrs)
        aux = None
        if armed:
            (new_params, self._opt_states, new_buffers, loss, drows_u,
             aux) = out
        else:
            new_params, self._opt_states, new_buffers, loss, drows_u = out
        # the chip is busy from here until the grad fetch below: issue
        # the announced next batch's shard fan-out NOW so its pull (and
        # the previous step's coalesced push) hides behind the device
        # computation
        self._issue_prefetch()
        for n, p in model.named_parameters():
            p._data = new_params[n]
        for n, b in model.named_buffers():
            if b is not None and n in new_buffers:
                b._data = new_buffers[n]
        if aux is not None:
            # publish after the prefetch issue: the aux fetch is the
            # step's one host sync, and the next pull already rides the
            # background executor by now
            rec = numerics.NumericsRecord(
                list(params) + ["embedding.rows"], aux,
                step=int(getattr(self.optimizer, "_global_step", 0)))
            numerics.publish(rec)
            self.last_numerics = rec
        grads_host = _np.asarray(drows_u)[:len(uniq)].astype(_np.float32)
        if self.prefetch_depth > 0 and (pipelined or self._inflight
                                        or self._announced):
            # pipeline active: defer — the next issue (or the next
            # synchronous pull, or flush) coalesces this push into one
            # round-trip with a pull.  The step's span id rides along
            # so the carrying RPC can link back to its producer
            self._pending_push.append((uniq, grads_host,
                                       step_span.span_id))
        else:
            # async host-side sparse update; overlaps the next device step
            self.embedding.communicator.push(uniq, grads_host)
        return Tensor(loss)

    def flush(self):
        # drain the pipeline first: an in-flight prefetch may carry a
        # coalesced push that has to land, and the deferred push of the
        # last step is still pending
        self._announced.clear()
        while self._inflight:
            inf = self._inflight.popleft()
            if self._settle_inflight(inf) is not None:
                self._end_prefetch_span(inf, "ok", drained=True)
        while self._pending_push:
            ids_p, g_p = self._pending_push.pop(0)[:2]
            self.embedding.table.push(ids_p, g_p)
        if self._prefetch_pool is not None:
            # don't leak a 'ps-prefetch' thread per PSTrainStep instance
            # (test suites and per-epoch rebuilds construct many); the
            # pool is re-created lazily if prefetch() is used again
            self._prefetch_pool.shutdown(wait=True)
            self._prefetch_pool = None
        self.embedding.flush()
