"""Overlapped, quantized PS transport (the DownpourWorker amortization +
EQuARX-style wire quantization): negotiated wire dtype with exact-f32
fallback, quantize/dequantize parity, the PSTrainStep prefetch pipeline
(pull/compute overlap + push/pull coalescing) incl. determinism under
injected ``ps.rpc``/``ps.pipeline`` faults and survival of an elastic
``reform()`` mid-prefetch, push (worker, seq) retry dedup, the cached
table dim, and the measured transport counters (``TransportStats``)."""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import optimizer
from paddle_tpu.distributed.ps import (DistributedEmbedding,
                                       HostEmbeddingTable, PSTrainStep)
from paddle_tpu.distributed.ps.device_table import (dequantize_rows,
                                                    normalize_wire,
                                                    quantize_rows)
from paddle_tpu.distributed.ps.service import (PsClient, PsServer,
                                               RemoteEmbeddingTable)
from paddle_tpu.framework import chaos


@pytest.fixture(autouse=True)
def _fresh_chaos():
    chaos.reset(0)
    yield
    chaos.reset(0)


def _server(table=None, **kw):
    srv = PsServer({"emb": table or HostEmbeddingTable(
        64, 8, optimizer="sgd", learning_rate=1.0)}, port=0, **kw)
    srv.start()
    return srv


# ---------------------------------------------------------------------------
# wire quantization: helper roundtrip + negotiated transport parity
# ---------------------------------------------------------------------------

class TestQuantizeHelpers:
    def test_normalize_aliases_and_rejects_typos(self):
        assert normalize_wire("bfloat16") == "bf16"
        assert normalize_wire("float32") == "f32"
        assert normalize_wire("s8") == "int8"
        with pytest.raises(ValueError, match="unknown PS wire dtype"):
            normalize_wire("fp8")

    def test_f32_roundtrip_exact(self):
        rows = np.random.default_rng(0).standard_normal(
            (16, 8)).astype(np.float32)
        out = dequantize_rows(quantize_rows(rows, "f32"), "f32")
        np.testing.assert_array_equal(out, rows)

    def test_bf16_roundtrip_tolerance(self):
        rows = np.random.default_rng(1).standard_normal(
            (64, 16)).astype(np.float32)
        out = dequantize_rows(quantize_rows(rows, "bf16"), "bf16")
        # bf16 keeps 8 mantissa bits: relative error < 2^-8
        np.testing.assert_allclose(out, rows, rtol=2 ** -8, atol=1e-30)

    def test_int8_roundtrip_tolerance_and_zero_rows(self):
        rng = np.random.default_rng(2)
        rows = rng.standard_normal((32, 8)).astype(np.float32)
        rows[5] = 0.0                      # all-zero row: scale guard
        bufs = quantize_rows(rows, "int8")
        assert bufs[0].dtype == np.int8 and bufs[1].shape == (32,)
        out = dequantize_rows(bufs, "int8")
        # symmetric per-row scale: |err| <= scale/2 = max|row| / 254
        err = np.abs(out - rows)
        bound = np.abs(rows).max(axis=1, keepdims=True) / 254 + 1e-12
        assert (err <= bound).all()
        np.testing.assert_array_equal(out[5], 0.0)


class TestWireNegotiation:
    @pytest.mark.parametrize("wire,rtol", [("bf16", 2 ** -8),
                                           ("int8", 2 ** -6)])
    def test_quantized_pull_push_roundtrip_vs_f32(self, wire, rtol):
        """Pull rows and push grads over the quantized wire land within
        the dtype's tolerance of the exact f32 transport."""
        t = HostEmbeddingTable(64, 8, optimizer="sgd", learning_rate=1.0)
        ref = t._table.copy()
        srv = _server(t)
        try:
            c = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype=wire)
            ids = np.arange(16)
            rows = c.pull("emb", ids)
            assert rows.dtype == np.float32
            np.testing.assert_allclose(rows, ref[ids], rtol=rtol,
                                       atol=1e-3)
            g = np.full((16, 8), 0.25, np.float32)   # exact in bf16/int8
            c.push("emb", ids, g)
            np.testing.assert_allclose(t._table[ids], ref[ids] - 0.25,
                                       rtol=rtol, atol=1e-2)
            c.bye()
        finally:
            srv.shutdown()

    def test_hello_handshake_reply(self):
        srv = _server()
        try:
            c = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="bf16")
            reply, _ = c._conns[0].rpc({"op": "hello", "wire": "bf16"})
            assert reply["wire"] == "bf16"
            assert set(reply["wire_dtypes"]) >= {"f32", "bf16", "int8"}
            assert c._push_wire(0) == "bf16"
        finally:
            srv.shutdown()

    def test_old_server_degrades_push_to_f32(self, monkeypatch):
        """A peer that predates the handshake (unknown 'hello' op) pins
        the push link to exact f32 instead of shipping bytes it cannot
        decode."""
        srv = _server()
        orig = srv._dispatch

        def old_dispatch(header, bufs):
            if header.get("op") in ("hello", "push_pull"):
                return {"ok": False,
                        "error": f"unknown op {header['op']!r}"}, []
            return orig(header, bufs)

        monkeypatch.setattr(srv, "_dispatch", old_dispatch)
        try:
            c = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="bf16")
            assert c._push_wire(0) == "f32"
            ids = np.arange(4)
            before = srv.tables["emb"]._table[ids].copy()
            c.push("emb", ids, np.ones((4, 8), np.float32))
            np.testing.assert_allclose(srv.tables["emb"]._table[ids],
                                       before - 1.0, rtol=1e-6)
        finally:
            srv.shutdown()

    def test_pull_decodes_reply_declared_wire(self, monkeypatch):
        """Reply-driven pull negotiation: an old server that ignores the
        requested wire dtype and answers raw f32 (no 'wire' key) is
        decoded correctly."""
        t = HostEmbeddingTable(16, 4, optimizer="sgd")
        srv = _server(t)
        orig = srv._dispatch

        def old_dispatch(header, bufs):
            if header.get("op") == "pull":       # pre-handshake server
                return {"ok": True}, [t.pull(bufs[0].astype(np.int64))]
            return orig(header, bufs)

        monkeypatch.setattr(srv, "_dispatch", old_dispatch)
        try:
            c = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="bf16")
            rows = c.pull("emb", np.arange(6))
            np.testing.assert_array_equal(rows, t._table[:6])
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------------
# push retry dedup: (worker, seq) stamps
# ---------------------------------------------------------------------------

class TestPushSeqDedup:
    def test_replayed_stamp_applies_once(self):
        """The lost-reply retry case: the same stamped push arriving
        twice (client retry after the server applied but the reply
        died) must apply exactly once."""
        t = HostEmbeddingTable(16, 4, optimizer="sgd", learning_rate=1.0)
        srv = _server(t)
        try:
            before = t._table.copy()
            header = {"op": "push", "table": "emb", "wire": "f32",
                      "worker": "w0", "seq": 7}
            bufs = [np.array([3]), np.ones((1, 4), np.float32)]
            r1, _ = srv._dispatch(dict(header), bufs)
            r2, _ = srv._dispatch(dict(header), bufs)   # the retry
            assert r1["dup"] is False and r2["dup"] is True
            np.testing.assert_allclose(t._table[3], before[3] - 1.0)
        finally:
            srv.shutdown()

    def test_push_pull_retry_dedups_push_but_serves_pull(self):
        t = HostEmbeddingTable(16, 4, optimizer="sgd", learning_rate=1.0)
        srv = _server(t)
        try:
            before = t._table.copy()
            header = {"op": "push_pull", "table": "emb", "wire": "f32",
                      "worker": "w0", "seq": 9, "n_push_bufs": 1}
            bufs = [np.array([2]), np.ones((1, 4), np.float32),
                    np.array([2, 5])]
            r1, rows1 = srv._dispatch(dict(header), bufs)
            r2, rows2 = srv._dispatch(dict(header), bufs)
            assert r1["dup"] is False and r2["dup"] is True
            np.testing.assert_allclose(t._table[2], before[2] - 1.0)
            # the pull half stays idempotent and served on the retry
            np.testing.assert_array_equal(rows1[0], rows2[0])
        finally:
            srv.shutdown()

    def test_distinct_pushes_get_distinct_seqs(self):
        t = HostEmbeddingTable(16, 4, optimizer="sgd", learning_rate=1.0)
        srv = _server(t)
        try:
            c = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="f32")
            before = t._table.copy()
            c.push("emb", np.array([1]), np.ones((1, 4), np.float32))
            c.push("emb", np.array([1]), np.ones((1, 4), np.float32))
            np.testing.assert_allclose(t._table[1], before[1] - 2.0)
            c.bye()
        finally:
            srv.shutdown()

    def test_failed_apply_does_not_consume_stamp(self):
        """A push whose APPLY failed (bad table here) must not burn its
        (worker, seq) stamp — the client's retry of a transient failure
        still has to land, not be dropped as a duplicate."""
        t = HostEmbeddingTable(16, 4, optimizer="sgd", learning_rate=1.0)
        srv = _server(t)
        try:
            before = t._table.copy()
            bufs = [np.array([4]), np.ones((1, 4), np.float32)]
            with pytest.raises(KeyError):
                srv._dispatch({"op": "push", "table": "nope",
                               "wire": "f32", "worker": "w0", "seq": 3},
                              bufs)
            # same stamp, healthy request: must APPLY, not dedup
            r, _ = srv._dispatch({"op": "push", "table": "emb",
                                  "wire": "f32", "worker": "w0",
                                  "seq": 3}, bufs)
            assert r["dup"] is False
            np.testing.assert_allclose(t._table[4], before[4] - 1.0)
        finally:
            srv.shutdown()

    def test_seq_window_and_worker_count_bounded(self):
        srv = _server()
        try:
            for s in range(srv.PUSH_SEQ_WINDOW + 10):
                srv._reserve_push({"worker": "w", "seq": s})
            assert len(srv._push_seen["w"]) == srv.PUSH_SEQ_WINDOW
            for w in range(srv.PUSH_SEQ_WORKERS + 10):
                srv._reserve_push({"worker": f"worker-{w}", "seq": 0})
            assert len(srv._push_seen) == srv.PUSH_SEQ_WORKERS
            # LRU eviction: the longest-quiet identities went first
            assert "worker-0" not in srv._push_seen
        finally:
            srv.shutdown()

    def test_new_client_incarnation_not_deduped(self):
        """A rebuilt client under the SAME worker_id (elastic re-form,
        restart in one process) restarts seq at 0; its stamps must not
        collide with the previous incarnation's window on a surviving
        server — the first post-re-form pushes would silently vanish."""
        t = HostEmbeddingTable(16, 4, optimizer="sgd", learning_rate=1.0)
        srv = _server(t)
        try:
            before = t._table.copy()
            c1 = PsClient([f"127.0.0.1:{srv.port}"], worker_id="rank-0",
                          wire_dtype="f32")
            c1.push("emb", np.array([1]), np.ones((1, 4), np.float32))
            c1.bye()
            c2 = PsClient([f"127.0.0.1:{srv.port}"], worker_id="rank-0",
                          wire_dtype="f32")
            c2.push("emb", np.array([1]), np.ones((1, 4), np.float32))
            np.testing.assert_allclose(t._table[1], before[1] - 2.0)
            c2.bye()
        finally:
            srv.shutdown()

    def test_pipeline_replay_reuses_seq_no_double_apply(self):
        """The dangerous half-failure: a push_pull whose push half
        LANDED but whose reply was lost.  The pipeline's replay must
        re-send the ORIGINAL seq so the server's dedup drops it — a
        fresh stamp would double-apply the gradient."""
        from concurrent.futures import Future
        t = HostEmbeddingTable(256, 9, optimizer="sgd", learning_rate=1.0)
        srv = _server(t)
        try:
            c = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="f32")
            step = _mk_ps_step(RemoteEmbeddingTable(c, "emb", 9))
            before = t._table.copy()
            ids_p = np.array([3])
            g_p = np.ones((1, 9), np.float32)
            seq = c._next_seq()
            c.push("emb", ids_p, g_p, seq=seq)    # "original landed"
            fut = Future()
            fut.set_exception(RuntimeError("reply lost"))
            step._settle_inflight({"key": ids_p, "epoch": None,
                                   "push": (ids_p, g_p, seq),
                                   "future": fut})
            # exactly ONE application despite the replay
            np.testing.assert_allclose(t._table[3], before[3] - 1.0)
            c.bye()
        finally:
            srv.shutdown()

    def test_retry_racing_slow_apply_rejected(self):
        """The reserve is claimed BEFORE the apply, so a retry arriving
        while the original apply is still running reads it as a dup —
        the concurrent double-apply window is closed."""
        srv = _server()
        try:
            header = {"worker": "w9", "seq": 5}
            assert srv._reserve_push(dict(header)) is True
            # original still applying: the racing retry must NOT pass
            assert srv._reserve_push(dict(header)) is False
            # a FAILED apply rolls the claim back; the retry then lands
            srv._unreserve_push(dict(header))
            assert srv._reserve_push(dict(header)) is True
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------------
# cached table dim: the empty-batch pull must not re-stat every call
# ---------------------------------------------------------------------------

class TestDimCache:
    def test_empty_pull_uses_cached_dim(self):
        srv = _server(HostEmbeddingTable(8, 5))
        try:
            c = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="f32")
            c.pull("emb", np.array([1, 2]))          # primes the cache
            s0 = c.transport_stats()["per_op"].get("stat", {"rpcs": 0})
            for _ in range(3):
                rows = c.pull("emb", np.zeros((0,), np.int64))
                assert rows.shape == (0, 5)
            s1 = c.transport_stats()["per_op"].get("stat", {"rpcs": 0})
            assert s1["rpcs"] == s0["rpcs"]          # no stat() burned
            c.bye()
        finally:
            srv.shutdown()

    def test_cold_empty_pull_stats_once(self):
        srv = _server(HostEmbeddingTable(8, 5))
        try:
            c = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="f32")
            for _ in range(3):
                assert c.pull("emb", np.zeros((0,), np.int64)
                              ).shape == (0, 5)
            assert c.transport_stats()["per_op"]["stat"]["rpcs"] == 1
            c.bye()
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------------
# transport accounting: measured bytes, rpc counts, latency histograms
# ---------------------------------------------------------------------------

class TestTransportCounters:
    def test_client_and_server_counters_agree(self):
        srv = _server()
        try:
            c = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="bf16")
            c.pull("emb", np.arange(8))
            c.push("emb", np.arange(8), np.ones((8, 8), np.float32))
            snap = c.transport_stats()
            assert snap["rpcs"] >= 3        # hello + pull + push
            assert snap["bytes_sent"] > 0 and snap["bytes_recv"] > 0
            assert snap["per_op"]["pull"]["rpcs"] == 1
            lat = snap["latency_ms"]["pull"]
            assert lat["count"] == 1 and lat["max"] >= 0
            ssnap = srv.transport.snapshot()
            # what the client sent is what the server received (and
            # vice versa) — the byte counters measure the same wire
            assert ssnap["bytes_recv"] == snap["bytes_sent"]
            assert ssnap["bytes_sent"] == snap["bytes_recv"]
            c.bye()
        finally:
            srv.shutdown()

    def test_stat_reports_both_ends_and_wire_dtypes(self):
        srv = _server()
        try:
            c = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="f32")
            c.pull("emb", np.arange(4))
            stat = c.stat()
            assert "bf16" in stat["wire_dtypes"]
            assert stat["transport"]["per_op"]["pull"]["rpcs"] == 1
            assert stat["client_transport"]["per_op"]["pull"]["rpcs"] == 1
            c.bye()
        finally:
            srv.shutdown()

    def test_bf16_wire_halves_row_bytes(self):
        """The headline byte claim, measured: the pull payload at bf16
        is ~half the f32 payload (ids/headers amortize out at this
        size)."""
        srv = _server(HostEmbeddingTable(4096, 64))
        try:
            ids = np.arange(2048)

            def bytes_for(wire):
                c = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype=wire)
                s0 = c.transport_stats()["bytes_recv"]
                c.pull("emb", ids)
                n = c.transport_stats()["bytes_recv"] - s0
                c.bye()
                return n

            assert bytes_for("bf16") / bytes_for("f32") < 0.55
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------------
# the prefetch pipeline: parity, determinism under faults, reform safety
# ---------------------------------------------------------------------------

def _mk_ps_step(table, seed=0, prefetch_depth=None, V=256, E=8,
                fields=4, dd=3):
    from paddle_tpu.models import WideDeepHost
    paddle.seed(seed)
    emb = DistributedEmbedding(V, E + 1, mode="sync", table=table)
    model = WideDeepHost(embedding_dim=E, num_fields=fields,
                         dense_dim=dd, hidden=(16,))
    opt = optimizer.Adam(learning_rate=1e-2,
                         parameters=model.parameters())

    def loss_fn(m, rows, x, y):
        return F.binary_cross_entropy_with_logits(m(rows, x), y).mean()

    kw = {} if prefetch_depth is None else {
        "prefetch_depth": prefetch_depth}
    return PSTrainStep(model, loss_fn, opt, emb,
                       transfer_dtype="float32", **kw)


def _disjoint_batches(n, B, fields, V, seed=0):
    """Batches with pairwise-disjoint id sets: pipeline staleness (pull
    N+1 not yet reflecting push N) cannot influence the trajectory, so
    pipelined and unpipelined runs must agree EXACTLY."""
    rng = np.random.default_rng(seed)
    per = B * fields
    perm = rng.permutation(V)[:n * per]
    return [perm[i * per:(i + 1) * per].reshape(B, fields)
            .astype(np.int64) for i in range(n)]


def _run_pipelined(step, batches, x, y, announce=True):
    losses = []
    if announce:
        step.prefetch(batches[0])
    for n, ids in enumerate(batches):
        if announce and n + 1 < len(batches):
            step.prefetch(batches[n + 1])
        losses.append(float(step(ids, x, y)))
    step.flush()
    return losses


class TestPrefetchPipeline:
    B, fields, steps = 8, 4, 6

    def _setup(self, prefetch_depth=None, wire="f32"):
        t = HostEmbeddingTable(256, 9, optimizer="sgd",
                               learning_rate=0.05, seed=0)
        srv = _server(t)
        c = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype=wire,
                     backoff_base=0.01)
        step = _mk_ps_step(RemoteEmbeddingTable(c, "emb", 9),
                           prefetch_depth=prefetch_depth)
        return t, srv, c, step

    def _data(self):
        rng = np.random.default_rng(3)
        batches = _disjoint_batches(self.steps, self.B, self.fields, 256)
        x = paddle.to_tensor(rng.standard_normal(
            (self.B, 3)).astype(np.float32))
        y = paddle.to_tensor(rng.integers(
            0, 2, (self.B, 1)).astype(np.float32))
        return batches, x, y

    def test_pipelined_matches_unpipelined_exactly(self):
        batches, x, y = self._data()
        t0, srv0, c0, step0 = self._setup(prefetch_depth=0)
        try:
            ref = _run_pipelined(step0, batches, x, y, announce=False)
            ref_table = srv0.tables["emb"]._table.copy()
            c0.bye()
        finally:
            srv0.shutdown()
        t1, srv1, c1, step1 = self._setup(prefetch_depth=1)
        try:
            got = _run_pipelined(step1, batches, x, y)
            np.testing.assert_allclose(got, ref, rtol=1e-6)
            # every push landed exactly once (sgd is additive, so the
            # final table pins the full push ledger)
            np.testing.assert_allclose(srv1.tables["emb"]._table,
                                       ref_table, rtol=1e-6)
            # and the steady state actually coalesced push+pull
            per_op = c1.transport_stats()["per_op"]
            assert per_op.get("push_pull", {}).get("rpcs", 0) >= \
                self.steps - 3
            c1.bye()
        finally:
            srv1.shutdown()

    @pytest.mark.parametrize("point,spec", [
        ("ps.pipeline", dict(mode="error", every=2)),
        ("ps.pipeline", dict(mode="latency", latency=0.02, every=2)),
        ("ps.rpc", dict(mode="error", every=5)),
    ])
    def test_deterministic_under_injected_faults(self, point, spec):
        """Injected prefetch/transport faults must neither crash, hang,
        lose a push, nor change the trajectory: the fallback paths
        (sync re-pull, push replay, RPC retry) reconverge on the exact
        clean-run math (ids disjoint, so staleness is immaterial)."""
        batches, x, y = self._data()
        t0, srv0, c0, step0 = self._setup(prefetch_depth=1)
        try:
            ref = _run_pipelined(step0, batches, x, y)
            ref_table = srv0.tables["emb"]._table.copy()
            c0.bye()
        finally:
            srv0.shutdown()
        t1, srv1, c1, step1 = self._setup(prefetch_depth=1)
        try:
            with chaos.inject(point, **spec):
                got = _run_pipelined(step1, batches, x, y)
                assert chaos.stats()[point]["trips"] >= 1
            np.testing.assert_allclose(got, ref, rtol=1e-6)
            np.testing.assert_allclose(srv1.tables["emb"]._table,
                                       ref_table, rtol=1e-6)
            c1.bye()
        finally:
            srv1.shutdown()

    def test_reform_mid_prefetch_discards_stale_and_survives(self):
        """An elastic ``reform()`` (epoch bump + server fence) landing
        between a prefetch's issue and its consume must neither
        deadlock nor let the stale pull/push land: the prefetched rows
        are discarded, the step re-pulls under the new epoch, and
        training continues."""
        batches, x, y = self._data()
        t, srv, c, step = self._setup(prefetch_depth=1)
        try:
            c.set_epoch(1, fence_servers=True)
            step.prefetch(batches[0])
            step.prefetch(batches[1])
            losses = [float(step(batches[0], x, y))]  # issues T(b1)
            assert step._inflight                     # prefetch in flight
            step._inflight[0]["future"].result()      # deterministic wait
            c.set_epoch(2, fence_servers=True)        # reform mid-prefetch
            # the rest of the run must discard the stale rows, re-pull
            # under the new epoch, and keep training — no deadlock, no
            # stale push/pull landing
            for n in range(1, len(batches)):
                if n + 1 < len(batches):
                    step.prefetch(batches[n + 1])
                losses.append(float(step(batches[n], x, y)))
            step.flush()
            assert np.isfinite(losses).all()
            # post-reform pushes (stamped with the new epoch) were
            # accepted: the last batch's rows moved off their init
            ids_last = np.unique(batches[-1])
            init = HostEmbeddingTable(256, 9, optimizer="sgd",
                                      learning_rate=0.05, seed=0)
            assert not np.allclose(t._table[ids_last],
                                   init._table[ids_last])
            c.bye()
        finally:
            srv.shutdown()

    def test_stale_epoch_coalesced_push_dropped_cleanly(self):
        """A coalesced push stamped pre-reform is rejected by the fence;
        the pipeline swallows the rejection (the re-form restored past
        it) and the following sync pull proceeds under the new epoch."""
        t, srv, c, step = self._setup(prefetch_depth=1)
        batches, x, y = self._data()
        try:
            c.set_epoch(1, fence_servers=True)
            ref = t._table.copy()
            # hand-plant a pending push + announce, then bump the epoch
            # on the SERVER only (a re-form this client hasn't adopted
            # yet — its next stamped RPC is stale)
            step._pending_push.append((np.array([7]),
                                       np.ones((1, 9), np.float32)))
            step.prefetch(batches[0])
            other = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="f32")
            other.set_epoch(2, fence_servers=True)
            step._issue_prefetch()                  # push_pull -> rejected
            got = step._consume_prefetch(batches[0])
            assert got is None                      # dropped, no raise
            np.testing.assert_array_equal(t._table, ref)  # push fenced out
            c.bye()
            other.bye()
        finally:
            srv.shutdown()

    def test_prefetch_noop_when_disabled(self):
        t, srv, c, step = self._setup(prefetch_depth=0)
        batches, x, y = self._data()
        try:
            step.prefetch(batches[0])
            assert not step._announced
            l = float(step(batches[0], x, y))
            assert np.isfinite(l)
            assert "push_pull" not in c.transport_stats()["per_op"]
            step.flush()
            c.bye()
        finally:
            srv.shutdown()


class TestQuantizedEndToEnd:
    def test_bf16_wire_pstrainstep_loss_parity(self):
        """End-to-end: PSTrainStep over the bf16 wire tracks the
        in-process (exact) run within bf16 tolerance and trains."""
        batches = _disjoint_batches(6, 8, 4, 256)
        rng = np.random.default_rng(5)
        x = paddle.to_tensor(rng.standard_normal((8, 3)).astype(np.float32))
        y = paddle.to_tensor(rng.integers(0, 2, (8, 1)).astype(np.float32))

        local = _mk_ps_step(HostEmbeddingTable(
            256, 9, optimizer="sgd", learning_rate=0.05, seed=0))
        ref = _run_pipelined(local, batches, x, y, announce=False)

        srv = _server(HostEmbeddingTable(256, 9, optimizer="sgd",
                                         learning_rate=0.05, seed=0))
        try:
            c = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="bf16")
            remote = _mk_ps_step(RemoteEmbeddingTable(c, "emb", 9))
            got = _run_pipelined(remote, batches, x, y)
            np.testing.assert_allclose(got, ref, rtol=0.02, atol=0.02)
            assert got[-1] < got[0]                  # it trains
            c.bye()
        finally:
            srv.shutdown()
