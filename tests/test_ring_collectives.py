"""Fused quantized ring collectives (parallel/ring.py) + the packed
int4 wire codec (distributed/wire.py) — PR 19's acceptance suite.

The contract under test, in order of importance:

1. **Exact f32 parity** — both ring primitives are BITWISE identical
   to the native ``psum_scatter`` / ``all_gather`` pair at dp=2 and
   dp=4, and the ring-enabled ``ShardedUpdateTrainStep`` at the f32
   wire reproduces the non-ring trajectory bit-for-bit (params AND
   moments, multi-step) — switching the schedule changes nothing on
   the exact leg.
2. The int4 codec round-trips within half a scale step, packs two
   nibbles per byte (odd widths carry a pad nibble the decoder trims
   via ``cols``), and its byte accounting is ~0.5 B/elem + 4 B/row.
3. Quantized ring legs drift boundedly and still train; the ring
   all-gather leaves every replica with BIT-IDENTICAL decoded values
   (single-source encoding, PR 8's discipline).
4. The ring lifts dp_meta's int8/int4 restriction (decode-before-sum)
   while the pmean path keeps rejecting them.
5. The PS wire extends to int4 behind the ``hello`` handshake: pulls
   and pushes engage int4 only when the server lists it; old peers pin
   f32 on BOTH directions (int4 predates no decoder tolerance).
6. The Pallas row-quantizer kernel (ops/pallas/ring_quant.py) is
   bitwise-identical to the traced wire codec in interpret mode.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import optimizer
from paddle_tpu.distributed.wire import (dequantize_rows,
                                         dequantize_rows_traced,
                                         normalize_wire, quantize_rows,
                                         quantize_rows_traced,
                                         wire_nbytes)
from paddle_tpu.framework import chaos
from paddle_tpu.parallel import make_mesh, set_mesh
from paddle_tpu.parallel.dp_meta import CompressedAllReduceTrainStep
from paddle_tpu.parallel.mesh import shard_map_compat
from paddle_tpu.parallel.ring import ring_all_gather, ring_reduce_scatter
from paddle_tpu.parallel.zero import ShardedUpdateTrainStep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp(seed=0):
    """Uneven leaves on purpose: a (1,)-bias below any dp width, a
    (33,)-bias divisible by nothing — the padding/boundary-tail path."""
    paddle.seed(seed)
    return nn.Sequential(nn.Linear(7, 33), nn.ReLU(), nn.Linear(33, 1))


def _loss_fn(m, x, y):
    return ((m(x) - y) ** 2).mean()


def _data(n=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 7)).astype(np.float32)
    y = (x @ rng.standard_normal((7, 1))).astype(np.float32)
    return x, y


def _params(model):
    return {n: np.asarray(p._data) for n, p in model.named_parameters()}


def _mesh(dp):
    mesh = make_mesh({"dp": dp}, devices=jax.devices()[:dp])
    set_mesh(mesh)
    return mesh


def _run(step, x, y, steps):
    T = paddle.to_tensor
    return [float(step(T(x), T(y))) for _ in range(steps)]


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.reset(0)
    yield
    chaos.reset(0)


def _ring_rs_ag(mesh, dp, chunk, wire):
    """shard_map'd ring pair: per-replica input row -> (scattered
    shards concatenated, every replica's gathered copy stacked)."""
    def body(xl):
        flat = xl.reshape(-1)
        s = ring_reduce_scatter(flat, "dp", axis_size=dp, chunk=chunk,
                                wire=wire)
        g = ring_all_gather(s, "dp", axis_size=dp, chunk=chunk,
                            wire=wire)
        return s, g[None]
    return shard_map_compat(body, mesh=mesh, in_specs=(P("dp"),),
                            out_specs=(P("dp"), P("dp")))


def _native_rs_ag(mesh, dp):
    def body(xl):
        flat = xl.reshape(-1).astype(jnp.float32)
        s = jax.lax.psum_scatter(flat, "dp", scatter_dimension=0,
                                 tiled=True)
        g = jax.lax.all_gather(s, "dp", tiled=True)
        return s, g[None]
    return shard_map_compat(body, mesh=mesh, in_specs=(P("dp"),),
                            out_specs=(P("dp"), P("dp")))


# ---------------------------------------------------------------------------
# int4 wire codec
# ---------------------------------------------------------------------------

class TestInt4Codec:
    def test_numpy_matches_traced_bitwise(self):
        rng = np.random.default_rng(3)
        rows = rng.standard_normal((5, 16)).astype(np.float32)
        q_np = quantize_rows(rows, "int4")
        q_tr = quantize_rows_traced(jnp.asarray(rows), "int4")
        np.testing.assert_array_equal(q_np[0], np.asarray(q_tr[0]))
        np.testing.assert_array_equal(q_np[1], np.asarray(q_tr[1]))
        np.testing.assert_array_equal(
            dequantize_rows(q_np, "int4"),
            np.asarray(dequantize_rows_traced(q_tr, "int4")))

    def test_packed_layout_and_roundtrip_bound(self):
        rng = np.random.default_rng(4)
        rows = rng.standard_normal((3, 64)).astype(np.float32) * 10
        packed, scale = quantize_rows(rows, "int4")
        assert packed.dtype == np.uint8
        assert packed.shape == (3, 32)          # two nibbles per byte
        back = dequantize_rows((packed, scale), "int4")
        # symmetric per-row scale: |err| <= scale/2 = max|row| / 14
        bound = np.asarray(scale)[:, None] * 0.5 + 1e-7
        assert (np.abs(back - rows) <= bound).all()

    def test_odd_width_pads_nibble_and_cols_trims(self):
        rng = np.random.default_rng(5)
        rows = rng.standard_normal((4, 9)).astype(np.float32)
        packed, scale = quantize_rows(rows, "int4")
        assert packed.shape == (4, 5)           # ceil(9 / 2)
        back = dequantize_rows((packed, scale), "int4", cols=9)
        assert back.shape == (4, 9)
        bound = np.asarray(scale)[:, None] * 0.5 + 1e-7
        assert (np.abs(back - rows) <= bound).all()
        # without cols the decoder returns the padded width
        assert dequantize_rows((packed, scale), "int4").shape == (4, 10)

    def test_zero_rows_decode_to_exact_zero(self):
        rows = jnp.zeros((2, 8), jnp.float32)
        back = dequantize_rows_traced(
            quantize_rows_traced(rows, "int4"), "int4")
        np.testing.assert_array_equal(np.asarray(back), 0.0)

    def test_extremes_saturate_not_wrap(self):
        # a row of +max/-max must hit exactly +-7 nibbles, never wrap
        rows = np.asarray([[8.0, -8.0, 0.0, 8.0]], np.float32)
        packed, scale = quantize_rows(rows, "int4")
        back = dequantize_rows((packed, scale), "int4")
        np.testing.assert_allclose(back, rows, rtol=1e-6)

    def test_normalize_aliases(self):
        assert normalize_wire("int4") == "int4"
        assert normalize_wire("s4") == "int4"
        assert normalize_wire("i4") == "int4"

    def test_wire_nbytes_int4(self):
        # 0.5 B/elem + one f32 scale per row, rounded to whole bytes
        assert wire_nbytes(1024, "int4", row=256) == 512 + 4 * 4
        assert wire_nbytes(1024, "int4") == 512 + 4
        # odd row width: each row rounds up to whole bytes
        assert wire_nbytes(36, "int4", row=9) == 4 * (5 + 4)
        assert wire_nbytes(1024, "int4", row=256) < \
            wire_nbytes(1024, "int8", row=256) < \
            wire_nbytes(1024, "bf16")


# ---------------------------------------------------------------------------
# ring primitives: exact leg bitwise, quantized legs bounded
# ---------------------------------------------------------------------------

class TestRingPrimitives:
    @pytest.mark.parametrize("dp", [2, 4])
    def test_f32_bitwise_matches_native_pair(self, dp):
        mesh = _mesh(dp)
        rng = np.random.default_rng(7)
        x = rng.standard_normal((dp, dp * 24)).astype(np.float32)
        s_r, g_r = _ring_rs_ag(mesh, dp, chunk=8, wire="f32")(x)
        s_n, g_n = _native_rs_ag(mesh, dp)(x)
        np.testing.assert_array_equal(np.asarray(s_r), np.asarray(s_n))
        np.testing.assert_array_equal(np.asarray(g_r), np.asarray(g_n))

    # the int4 cases are the heaviest of the tier-1 run (15-25 s each, the
    # nibble pack/unpack under an unrolled ring): slow, the int8 ones stay
    @pytest.mark.parametrize("dp", [2, 4])
    @pytest.mark.parametrize("wire,qmax", [
        ("int8", 127.0),
        pytest.param("int4", 7.0, marks=pytest.mark.slow)])
    def test_quantized_rs_tracks_exact_sum(self, dp, wire, qmax):
        """Each of the dp-1 hops re-encodes the f32 partial, so the
        error is at most (dp-1) half-scale steps of the largest
        partial — assert an explicit analytic envelope."""
        mesh = _mesh(dp)
        rng = np.random.default_rng(8)
        x = rng.standard_normal((dp, dp * 24)).astype(np.float32)
        s_r, _ = _ring_rs_ag(mesh, dp, chunk=8, wire=wire)(x)
        want = x.sum(axis=0)                    # exact reduce
        # scatter layout: replica i owns chunk i of the summed vector
        got = np.asarray(s_r).reshape(-1)
        # largest partial along any hop chain is bounded by the sum of
        # per-replica magnitudes; the initial encode plus each of the
        # dp-1 re-encodes adds <= scale/2, with scale <= part_max/qmax
        # (factor 2 margin for scale interplay across hops)
        part_max = np.abs(x).sum(axis=0).max()
        bound = dp * (part_max / qmax) + 1e-6
        assert np.abs(got - want).max() <= bound

    @pytest.mark.parametrize("wire,qmax", [
        ("int8", 127.0),
        pytest.param("int4", 7.0, marks=pytest.mark.slow)])
    def test_quantized_ag_bitwise_across_replicas(self, wire, qmax):
        """Every replica decodes the SOURCE's single encoding: the
        gathered copies must be bit-identical across the ring, and
        within half a scale step of the true shard."""
        dp = 4
        mesh = _mesh(dp)
        rng = np.random.default_rng(9)
        x = rng.standard_normal((dp, dp * 16)).astype(np.float32)
        _, g = _ring_rs_ag(mesh, dp, chunk=8, wire=wire)(x)
        g = np.asarray(g)                       # (dp, full)
        for r in range(1, dp):
            np.testing.assert_array_equal(g[0], g[r])

    def test_indivisible_payload_raises(self):
        mesh = _mesh(2)
        x = np.ones((2, 10), np.float32)        # 5 per replica, chunk 4
        with pytest.raises(ValueError, match="not divisible"):
            _ring_rs_ag(mesh, 2, chunk=4, wire="int8")(x)


# ---------------------------------------------------------------------------
# ring-enabled sharded update: exact parity + bounded quantized drift
# ---------------------------------------------------------------------------

class TestRingTrainStep:
    @pytest.mark.parametrize("dp", [2, 4])
    def test_f32_ring_bitwise_matches_unfused(self, dp):
        """Multi-step BITWISE parity of losses, params AND moments
        between ring=True and ring=False at the f32 wire."""
        mesh = _mesh(dp)
        x, y = _data()
        m_r, m_u = _mlp(), _mlp()
        o_r = optimizer.Adam(learning_rate=0.05,
                             parameters=m_r.parameters())
        o_u = optimizer.Adam(learning_rate=0.05,
                             parameters=m_u.parameters())
        r = ShardedUpdateTrainStep(m_r, _loss_fn, o_r, mesh=mesh,
                                   wire_dtype="f32", chunk=8, ring=True)
        u = ShardedUpdateTrainStep(m_u, _loss_fn, o_u, mesh=mesh,
                                   wire_dtype="f32", chunk=8, ring=False)
        assert _run(r, x, y, 6) == _run(u, x, y, 6)
        for (n, pr), (_, pu) in zip(m_r.named_parameters(),
                                    m_u.named_parameters()):
            np.testing.assert_array_equal(
                np.asarray(pr._data), np.asarray(pu._data), err_msg=n)
        for n, slots in r._opt_states.items():
            for k, v in slots.items():
                np.testing.assert_array_equal(
                    np.asarray(v), np.asarray(u._opt_states[n][k]),
                    err_msg=f"{n}/{k}")

    @pytest.mark.parametrize("wire,tol", [("bf16", 2e-2), ("int8", 8e-2),
                                          ("int4", 4e-1)])
    def test_quantized_ring_bounded_drift_and_trains(self, wire, tol):
        mesh = _mesh(2)
        x, y = _data()
        m_q, m_f = _mlp(), _mlp()
        o_q = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                 parameters=m_q.parameters())
        o_f = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                 parameters=m_f.parameters())
        q = ShardedUpdateTrainStep(m_q, _loss_fn, o_q, mesh=mesh,
                                   wire_dtype=wire, chunk=8, ring=True)
        f = ShardedUpdateTrainStep(m_f, _loss_fn, o_f, mesh=mesh,
                                   wire_dtype="f32", chunk=8, ring=True)
        lq = _run(q, x, y, 6)
        lf = _run(f, x, y, 6)
        assert lq[-1] < lq[0] * 0.5             # it trains
        for a, b in zip(lq, lf):                # and tracks the exact run
            assert abs(a - b) <= tol * max(1.0, abs(b))

    @pytest.mark.slow      # heavy for the 870 s tier-1 cap (PR 21): -m slow
    def test_ring_replicas_hold_identical_params(self):
        """Determinism across runs at dp=4 int4: only possible if all
        replicas left every step with identical parameters."""
        mesh = _mesh(4)
        x, y = _data()
        runs = []
        for _ in range(2):
            m = _mlp()
            o = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                   parameters=m.parameters())
            s = ShardedUpdateTrainStep(m, _loss_fn, o, mesh=mesh,
                                       wire_dtype="int4", chunk=8,
                                       ring=True)
            runs.append((_run(s, x, y, 3), _params(m)))
        assert runs[0][0] == runs[1][0]
        for n in runs[0][1]:
            np.testing.assert_array_equal(runs[0][1][n], runs[1][1][n])

    def test_ring_wire_bytes_ladder(self):
        """The analytic per-step byte accounting keeps the codec
        ladder (int4 < int8 < bf16 < f32), and at the production chunk
        of 256 the scale overhead stays under the op_bench ceilings."""
        mesh = _mesh(2)
        paddle.seed(0)
        m = nn.Sequential(nn.Linear(256, 256), nn.ReLU(),
                          nn.Linear(256, 16))
        o = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                               parameters=m.parameters())
        s = ShardedUpdateTrainStep(m, _loss_fn, o, mesh=mesh,
                                   wire_dtype="f32", chunk=256,
                                   ring=True)
        totals = {}
        for wire in ("f32", "bf16", "int8", "int4"):
            b = s.collective_wire_bytes(wire=wire)
            totals[wire] = b["reduce_scatter"] + b["all_gather"]
        assert totals["int4"] < totals["int8"] < totals["bf16"] \
            < totals["f32"]
        assert totals["int4"] <= 0.14 * totals["f32"]
        assert totals["int8"] <= 0.26 * totals["f32"]

    @pytest.mark.slow      # heavy for the 870 s tier-1 cap (PR 21): -m slow
    def test_chaos_collective_deterministic_under_ring(self):
        """The zero.collective fault point wraps the ring path too:
        an injected error is retried to a bit-identical trajectory."""
        mesh = _mesh(2)
        x, y = _data()

        def run(with_fault):
            chaos.reset(11)
            m = _mlp()
            o = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                   parameters=m.parameters())
            s = ShardedUpdateTrainStep(m, _loss_fn, o, mesh=mesh,
                                       wire_dtype="int4", chunk=8,
                                       ring=True)
            if with_fault:
                with chaos.inject("zero.collective", mode="error",
                                  nth=3, n_times=1) as spec:
                    losses = _run(s, x, y, 4)
                assert spec.trips == 1
            else:
                losses = _run(s, x, y, 4)
            return losses, _params(m)

        clean, p_clean = run(False)
        faulted, p_faulted = run(True)
        assert clean == faulted
        for n in p_clean:
            np.testing.assert_array_equal(p_clean[n], p_faulted[n])


# ---------------------------------------------------------------------------
# dp_meta: the ring lifts the int8 restriction, the pmean path keeps it
# ---------------------------------------------------------------------------

class TestCompressedRing:
    def test_pmean_path_still_rejects_int8(self):
        mesh = _mesh(2)
        m = _mlp()
        o = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                               parameters=m.parameters())
        with pytest.raises(ValueError):
            CompressedAllReduceTrainStep(m, _loss_fn, o, mesh=mesh,
                                         compress_dtype="int8",
                                         ring=False)

    @pytest.mark.parametrize("wire,tol", [("int8", 8e-2), ("int4", 4e-1)])
    def test_ring_admits_quantized_compress(self, wire, tol):
        """decode-before-sum makes int8/int4 legal compress dtypes on
        the ring path — and the run stays close to the exact one."""
        mesh = _mesh(2)
        x, y = _data()
        m_q, m_f = _mlp(), _mlp()
        o_q = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                 parameters=m_q.parameters())
        o_f = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                 parameters=m_f.parameters())
        q = CompressedAllReduceTrainStep(m_q, _loss_fn, o_q, mesh=mesh,
                                         compress_dtype=wire, ring=True,
                                         chunk=8)
        f = CompressedAllReduceTrainStep(m_f, _loss_fn, o_f, mesh=mesh,
                                         compress_dtype="float32")
        lq = _run(q, x, y, 5)
        lf = _run(f, x, y, 5)
        assert lq[-1] < lq[0] * 0.7
        for a, b in zip(lq, lf):
            assert abs(a - b) <= tol * max(1.0, abs(b))

    def test_ring_f32_close_to_pmean_path(self):
        """f32 ring allreduce (reduce-scatter + all-gather) differs
        from the pmean only in reduction order — float tolerance."""
        mesh = _mesh(2)
        x, y = _data()
        m_r, m_p = _mlp(), _mlp()
        o_r = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                 parameters=m_r.parameters())
        o_p = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                 parameters=m_p.parameters())
        r = CompressedAllReduceTrainStep(m_r, _loss_fn, o_r, mesh=mesh,
                                         compress_dtype="float32",
                                         ring=True, chunk=8)
        p = CompressedAllReduceTrainStep(m_p, _loss_fn, o_p, mesh=mesh,
                                         compress_dtype="float32",
                                         ring=False)
        np.testing.assert_allclose(_run(r, x, y, 4), _run(p, x, y, 4),
                                   rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# PS transport: int4 behind the hello handshake
# ---------------------------------------------------------------------------

class TestPsInt4Transport:
    def _server(self, dim=9):
        from paddle_tpu.distributed.ps import HostEmbeddingTable
        from paddle_tpu.distributed.ps.service import PsServer
        t = HostEmbeddingTable(64, dim, optimizer="sgd",
                               learning_rate=1.0, seed=0)
        return t, PsServer({"emb": t}, port=0).start()

    def test_int4_pull_push_roundtrip_odd_dim(self):
        """dim=9 exercises the pad nibble + cols declaration on both
        the pull reply and the push header."""
        from paddle_tpu.distributed.ps.service import PsClient
        t, srv = self._server(dim=9)
        ref = t._table.copy()
        try:
            c = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="int4")
            ids = np.arange(16)
            rows = c.pull("emb", ids)
            assert rows.shape == (16, 9) and rows.dtype == np.float32
            scale = np.abs(ref[ids]).max(axis=1, keepdims=True) / 7.0
            assert (np.abs(rows - ref[ids]) <= scale * 0.5 + 1e-7).all()
            g = np.full((16, 9), 0.25, np.float32)   # exact in int4
            c.push("emb", ids, g)
            np.testing.assert_allclose(t._table[ids], ref[ids] - 0.25,
                                       rtol=1e-6, atol=1e-6)
            c.bye()
        finally:
            srv.shutdown()

    def test_hello_advertises_int4(self):
        from paddle_tpu.distributed.ps.service import PsClient
        _, srv = self._server()
        try:
            c = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="int4")
            reply, _ = c._conns[0].rpc({"op": "hello", "wire": "int4"})
            assert "int4" in reply["wire_dtypes"]
            assert c._push_wire(0) == "int4"
            assert c._pull_wire(0) == "int4"
        finally:
            srv.shutdown()

    def test_old_server_pins_f32_both_directions(self, monkeypatch):
        """A pre-int4 server (no hello) must degrade BOTH the pull
        request and the push encoding to f32 — an old pull path would
        raise on a dtype it cannot name, so the client never asks."""
        from paddle_tpu.distributed.ps.service import PsClient
        t, srv = self._server(dim=8)
        orig = srv._dispatch

        def old_dispatch(header, bufs):
            if header.get("op") in ("hello", "push_pull"):
                return {"ok": False,
                        "error": f"unknown op {header['op']!r}"}, []
            assert header.get("wire", "f32") == "f32", \
                "client sent a quantized wire to an old server"
            return orig(header, bufs)

        monkeypatch.setattr(srv, "_dispatch", old_dispatch)
        try:
            c = PsClient([f"127.0.0.1:{srv.port}"], wire_dtype="int4")
            assert c._push_wire(0) == "f32"
            assert c._pull_wire(0) == "f32"
            ids = np.arange(4)
            rows = c.pull("emb", ids)
            np.testing.assert_array_equal(rows, t._table[ids])
            c.push("emb", ids, np.ones((4, 8), np.float32))
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------------
# Pallas row-quantizer kernel: interpret-mode differential oracle
# ---------------------------------------------------------------------------

class TestRingQuantKernel:
    @pytest.fixture(autouse=True)
    def _interpret(self, monkeypatch):
        from paddle_tpu.ops.pallas import ring_quant
        monkeypatch.setattr(ring_quant, "_INTERPRET", True)
        yield

    @pytest.mark.parametrize("shape", [(300, 256), (7, 128),
                                       (1024, 384)])
    @pytest.mark.parametrize("wire", ["int8", "int4"])
    def test_bitwise_matches_traced_codec(self, shape, wire):
        from paddle_tpu.ops.pallas.ring_quant import (ring_quant_rows,
                                                      xla_reference)
        rng = np.random.default_rng(17)
        rows = jnp.asarray(rng.standard_normal(shape)
                           .astype(np.float32))
        got = ring_quant_rows(rows, wire)
        want = xla_reference(rows, wire)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_off_lane_width_falls_back_to_traced(self):
        from paddle_tpu.ops.pallas.ring_quant import (ring_quant_rows,
                                                      xla_reference)
        rows = jnp.asarray(np.random.default_rng(0)
                           .standard_normal((5, 33)).astype(np.float32))
        got = ring_quant_rows(rows, "int8")
        want = xla_reference(rows, "int8")
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_zero_rows_quantize_to_zero(self):
        from paddle_tpu.ops.pallas.ring_quant import ring_quant_rows
        q, scale = ring_quant_rows(jnp.zeros((4, 128), jnp.float32),
                                   "int8")
        np.testing.assert_array_equal(np.asarray(q), 0)
        np.testing.assert_array_equal(np.asarray(scale), 1.0)


# ---------------------------------------------------------------------------
# gate plumbing: op_bench suite keys + the observatory's zero leg
# ---------------------------------------------------------------------------

class TestRingGatePlumbing:
    def test_baseline_and_thresholds_cover_ring_suite(self):
        import sys
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import op_bench
        names = {c["name"] for c in op_bench.RING_COLLECTIVES_SUITE}
        assert len(names) == 8
        with open(os.path.join(REPO, "tools",
                               "op_bench_baseline.json")) as f:
            base = {r["name"] for r in json.load(f)}
        with open(os.path.join(REPO, "tools",
                               "op_bench_thresholds.json")) as f:
            thr = set(json.load(f))
        assert names <= base
        assert names <= thr

    def test_ring_wire_ratio_ceilings_pinned(self):
        import sys
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import op_bench
        caps = op_bench.RING_WIRE_RATIO_MAX
        assert caps["bf16"] <= 0.51
        assert caps["int8"] <= 0.26
        assert caps["int4"] <= 0.14

    def test_zero_collective_bytes_reach_run_summary(self):
        """The stat the ZeRO step publishes must flow through the
        runlog summary whitelist — that is the series the ci ring lane
        asserts an IMPROVEMENT on."""
        from paddle_tpu.framework import monitor, runlog
        monitor.stat_set("zero_collective_bytes_per_step", 12345)
        try:
            rec = runlog.capture("test", label="ring")
            assert rec["summary"][
                "zero_collective_bytes_per_step"] == 12345.0
        finally:
            monitor.stat_set("zero_collective_bytes_per_step", 0)
