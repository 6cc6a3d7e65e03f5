"""Pallas kernel analysis plane test suite.

Static half (framework.analysis.pallas_kernels, PTA601-606): per-rule
positive/negative fixtures over hand-built pallas_call sites, pragma
suppression on call headers and body lines, and the in-tree flash
regression (non-divisible shape traced clean at zero errors AND zero
warnings).  Runtime half (ops.pallas.verify): boundary-corpus
determinism, agree/diverge contracts with operand naming, the
disarmed-is-exactly-one-flag-lookup discipline, chaos swallow, and the
fixture-pinned static+runtime same-label acceptance."""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from paddle_tpu.framework import chaos, monitor
from paddle_tpu.framework.analysis import (RULES, analyze_kernels,
                                           trace_kernels)
from paddle_tpu.framework.flags import get_flags, set_flags
from paddle_tpu.ops.pallas import verify

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

FIXTURE = os.path.join(REPO, "tests", "fixtures", "pallas_oob.py")

B = 128


@pytest.fixture(autouse=True)
def _clean_verify_flags():
    saved = get_flags(["pallas_verify", "pallas_vmem_budget_kb"])
    yield
    set_flags(saved)
    chaos.reset()


def _copy_kernel(x_ref, out_ref):
    out_ref[...] = x_ref[...] * 2.0


def _call(grid, in_spec, out_spec, out_shape, kernel=_copy_kernel):
    def run(x):
        return pl.pallas_call(
            kernel, grid=grid, in_specs=[in_spec], out_specs=out_spec,
            out_shape=out_shape)(x)
    return run


def f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def bf16(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


def _rules(report):
    return sorted({d.rule for d in report.diagnostics})


# ---------------------------------------------------------------------------
# model extraction
# ---------------------------------------------------------------------------


class TestTraceKernels:
    def test_captures_grid_blocks_and_labels(self):
        run = _call((2,), pl.BlockSpec((B, B), lambda i: (i, 0)),
                    pl.BlockSpec((B, B), lambda i: (i, 0)),
                    f32(2 * B, B))
        models = trace_kernels(run, f32(2 * B, B))
        assert len(models) == 1
        m = models[0]
        assert m.grid == (2,)
        assert [op.label for op in m.inputs] == ["x"]
        assert [op.label for op in m.outputs] == ["out"]
        assert m.inputs[0].block_shape == (B, B)
        assert m.call_line and m.call_file and m.body_tree is not None

    def test_plain_xla_program_yields_no_models(self):
        assert trace_kernels(lambda x: x * 2 + 1, f32(8, 8)) == []
        rep = analyze_kernels(lambda x: jnp.tanh(x).sum(), f32(8, 8),
                              name="plain")
        assert rep.errors == [] and rep.warnings == [], rep.to_text()

    def test_rules_registered_on_pallas_frontend(self):
        for rid in ("PTA601", "PTA602", "PTA603", "PTA604", "PTA605",
                    "PTA606"):
            assert rid in RULES and RULES[rid].frontend == "pallas"


# ---------------------------------------------------------------------------
# per-rule positive/negative fixtures
# ---------------------------------------------------------------------------


class TestPallasRules:
    def test_pta601_floored_grid_positive(self):
        # 300 rows, 128-blocks, floored grid: out tail never written
        run = _call((300 // B,), pl.BlockSpec((B, B), lambda i: (i, 0)),
                    pl.BlockSpec((B, B), lambda i: (i, 0)), f32(300, B))
        rep = analyze_kernels(run, f32(300, B), name="k")
        msgs = [d.message for d in rep.diagnostics if d.rule == "PTA601"]
        assert msgs and "k.out" in msgs[0] and "256 of 300" in msgs[0]

    def test_pta601_divisible_negative(self):
        run = _call((2,), pl.BlockSpec((B, B), lambda i: (i, 0)),
                    pl.BlockSpec((B, B), lambda i: (i, 0)),
                    f32(2 * B, B))
        rep = analyze_kernels(run, f32(2 * B, B), name="k")
        assert rep.errors == [] and rep.warnings == [], rep.to_text()

    def test_pta601_unmasked_input_overrun_positive(self):
        # cdiv grid: the input's last block overruns 300 with no mask
        run = _call((3,), pl.BlockSpec((B, B), lambda i: (i, 0)),
                    pl.BlockSpec((B, B), lambda i: (i, 0)), f32(3 * B, B))
        rep = analyze_kernels(run, f32(300, B), name="k")
        msgs = [d.message for d in rep.diagnostics if d.rule == "PTA601"]
        assert msgs and "k.x" in msgs[0] and "does not divide" in msgs[0]

    def test_pta601_masked_input_overrun_negative(self):
        def masked_kernel(x_ref, out_ref):
            row = pl.program_id(0) * B + \
                jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
            out_ref[...] = jnp.where(row < 300, x_ref[...] * 2.0, 0.0)

        run = _call((3,), pl.BlockSpec((B, B), lambda i: (i, 0)),
                    pl.BlockSpec((B, B), lambda i: (i, 0)),
                    f32(3 * B, B), kernel=masked_kernel)
        rep = analyze_kernels(run, f32(300, B), name="k")
        assert _rules(rep) == []

    def test_pta602_bf16_dot_positive_and_negative(self):
        def dot_kernel(x_ref, out_ref):
            out_ref[...] = jnp.dot(x_ref[...], x_ref[...])

        def safe_kernel(x_ref, out_ref):
            out_ref[...] = jax.lax.dot(
                x_ref[...], x_ref[...],
                preferred_element_type=jnp.float32).astype(jnp.bfloat16)

        spec = pl.BlockSpec((B, B), lambda i: (i, 0))
        rep = analyze_kernels(
            _call((1,), spec, spec, bf16(B, B), kernel=dot_kernel),
            bf16(B, B), name="k")
        assert "PTA602" in _rules(rep)
        assert any("k" in d.message and "preferred_element_type"
                   in d.message for d in rep.diagnostics)
        rep = analyze_kernels(
            _call((1,), spec, spec, bf16(B, B), kernel=safe_kernel),
            bf16(B, B), name="k")
        assert "PTA602" not in _rules(rep)

    def test_pta602_f32_dot_negative(self):
        def dot_kernel(x_ref, out_ref):
            out_ref[...] = jnp.dot(x_ref[...], x_ref[...])

        spec = pl.BlockSpec((B, B), lambda i: (i, 0))
        rep = analyze_kernels(
            _call((1,), spec, spec, f32(B, B), kernel=dot_kernel),
            f32(B, B), name="k")
        assert "PTA602" not in _rules(rep)

    def test_pta603_ignored_grid_axis_positive(self):
        run = _call((2, 2), pl.BlockSpec((B, B), lambda r, i: (i, 0)),
                    pl.BlockSpec((B, B), lambda r, i: (i, 0)),
                    f32(2 * B, B))
        rep = analyze_kernels(run, f32(2 * B, B), name="k")
        msgs = [d.message for d in rep.diagnostics if d.rule == "PTA603"]
        assert msgs and "k.out" in msgs[0] and "ignores grid axis 0" \
            in msgs[0]

    def test_pta603_all_axes_used_negative(self):
        run = _call((2, 2), pl.BlockSpec((B, B), lambda r, i: (r, i)),
                    pl.BlockSpec((B, B), lambda r, i: (r, i)),
                    f32(2 * B, 2 * B))
        rep = analyze_kernels(run, f32(2 * B, 2 * B), name="k")
        assert "PTA603" not in _rules(rep)

    def test_pta603_noninjective_positive(self):
        run = _call((4,), pl.BlockSpec((B, B), lambda i: (i, 0)),
                    pl.BlockSpec((B, B), lambda i: (i // 2, 0)),
                    f32(2 * B, B))
        rep = analyze_kernels(run, f32(4 * B, B), name="k")
        msgs = [d.message for d in rep.diagnostics if d.rule == "PTA603"]
        assert msgs and "not injective" in msgs[0]

    def test_pta604_unanchored_iota_positive(self):
        def bad_mask(x_ref, out_ref):
            row = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
            out_ref[...] = jnp.where(row < 100, x_ref[...], 0.0)

        spec = pl.BlockSpec((B, B), lambda i: (i, 0))
        rep = analyze_kernels(
            _call((2,), spec, spec, f32(2 * B, B), kernel=bad_mask),
            f32(2 * B, B), name="k")
        msgs = [d.message for d in rep.diagnostics if d.rule == "PTA604"]
        assert msgs and "block origin" in msgs[0]

    def test_pta604_anchored_iota_negative(self):
        def good_mask(x_ref, out_ref):
            row = pl.program_id(0) * B + \
                jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
            out_ref[...] = jnp.where(row < 100, x_ref[...], 0.0)

        spec = pl.BlockSpec((B, B), lambda i: (i, 0))
        rep = analyze_kernels(
            _call((2,), spec, spec, f32(2 * B, B), kernel=good_mask),
            f32(2 * B, B), name="k")
        assert "PTA604" not in _rules(rep)

    def test_pta604_single_block_negative(self):
        def bare_mask(x_ref, out_ref):
            row = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
            out_ref[...] = jnp.where(row < 100, x_ref[...], 0.0)

        spec = pl.BlockSpec((B, B), lambda i: (i, 0))
        rep = analyze_kernels(
            _call((1,), spec, spec, f32(B, B), kernel=bare_mask),
            f32(B, B), name="k")
        assert "PTA604" not in _rules(rep)

    def test_pta605_budget_positive_negative_and_disable(self):
        spec = pl.BlockSpec((B, B), lambda i: (i, 0))
        run = _call((2,), spec, spec, f32(2 * B, B))
        # 2x (64 KB in + 64 KB out) = 256 KB > 100 KB budget
        rep = analyze_kernels(run, f32(2 * B, B), name="k",
                              vmem_budget_kb=100)
        msgs = [d.message for d in rep.diagnostics if d.rule == "PTA605"]
        assert msgs and "VMEM" in msgs[0] and "100 KB budget" in msgs[0]
        rep = analyze_kernels(run, f32(2 * B, B), name="k",
                              vmem_budget_kb=16384)
        assert "PTA605" not in _rules(rep)
        rep = analyze_kernels(run, f32(2 * B, B), name="k",
                              vmem_budget_kb=0)      # <=0 disables
        assert "PTA605" not in _rules(rep)

    def test_pta606_traced_if_positive(self):
        def branchy(x_ref, out_ref):
            if x_ref[0, 0] > 0:
                out_ref[...] = x_ref[...]
            else:
                out_ref[...] = -x_ref[...]

        spec = pl.BlockSpec((B, B), lambda i: (i, 0))
        rep = analyze_kernels(
            _call((1,), spec, spec, f32(B, B), kernel=branchy),
            f32(B, B), name="k")
        msgs = [d.message for d in rep.diagnostics if d.rule == "PTA606"]
        assert msgs and "Python `if`" in msgs[0]

    def test_pta606_static_kwarg_branch_negative(self):
        import functools

        def kernel(x_ref, out_ref, *, negate):
            if negate:
                out_ref[...] = -x_ref[...]
            else:
                out_ref[...] = x_ref[...]

        spec = pl.BlockSpec((B, B), lambda i: (i, 0))
        rep = analyze_kernels(
            _call((1,), spec, spec, f32(B, B),
                  kernel=functools.partial(kernel, negate=True)),
            f32(B, B), name="k")
        assert "PTA606" not in _rules(rep)

    def test_pta606_pid_for_loop_positive(self):
        def loopy(x_ref, out_ref):
            n = pl.program_id(0)
            acc = x_ref[...]
            for _ in range(n):
                acc = acc + 1.0
            out_ref[...] = acc

        spec = pl.BlockSpec((B, B), lambda i: (i, 0))
        rep = analyze_kernels(
            _call((2,), spec, spec, f32(2 * B, B), kernel=loopy),
            f32(2 * B, B), name="k")
        assert "PTA606" in _rules(rep)


# ---------------------------------------------------------------------------
# pragma suppression
# ---------------------------------------------------------------------------


class TestSuppression:
    def test_call_header_pragma_suppresses_601_603(self):
        def run(x):
            return pl.pallas_call(  # pta: disable=PTA601,PTA603
                _copy_kernel,
                grid=(2, 300 // B),
                in_specs=[pl.BlockSpec((B, B), lambda r, i: (i, 0))],
                out_specs=pl.BlockSpec((B, B), lambda r, i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((300, B), jnp.float32),
            )(x)

        rep = analyze_kernels(run, f32(300, B), name="k")
        assert "PTA601" not in _rules(rep)
        assert "PTA603" not in _rules(rep)

    def test_body_line_pragma_suppresses_602(self):
        def dot_kernel(x_ref, out_ref):
            out_ref[...] = jnp.dot(  # pta: disable=PTA602
                x_ref[...], x_ref[...])

        spec = pl.BlockSpec((B, B), lambda i: (i, 0))
        rep = analyze_kernels(
            _call((1,), spec, spec, bf16(B, B), kernel=dot_kernel),
            bf16(B, B), name="k")
        assert "PTA602" not in _rules(rep)

    def test_disable_kwarg_filters(self):
        run = _call((300 // B,), pl.BlockSpec((B, B), lambda i: (i, 0)),
                    pl.BlockSpec((B, B), lambda i: (i, 0)), f32(300, B))
        rep = analyze_kernels(run, f32(300, B), name="k",
                              disable=["PTA601"])
        assert "PTA601" not in _rules(rep)


# ---------------------------------------------------------------------------
# in-tree regression: the kernel tier stays clean
# ---------------------------------------------------------------------------


class TestInTreeKernels:
    def test_flash_non_divisible_traced_clean(self):
        from paddle_tpu.ops.pallas import flash_attention as fa

        def loss(q, k, v):
            return fa.flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()

        sds = bf16(1, 1300, 2, 64)
        rep = analyze_kernels(jax.grad(loss, argnums=(0, 1, 2)),
                              sds, sds, sds, name="flash")
        assert rep.errors == [] and rep.warnings == [], rep.to_text()

    @pytest.mark.parametrize("blocks", [(256, 256), (256, 128)])
    def test_flash_two_level_traced_clean(self, blocks):
        """The GPT-2 cells' call on the two-level nest: K/V (or Q/dO)
        resident through an index map that ignores the second grid axis,
        the innermost axis a loop in the kernel, and the fused backward's
        dQ written through such a map from a scratch that lives across the
        axis.  No pass may mistake the resident operand for a race or the
        loop for ref-driven control."""
        from paddle_tpu.framework.analysis.pallas_kernels import (
            trace_kernels)
        from paddle_tpu.ops.pallas import autotune
        from paddle_tpu.ops.pallas import flash_attention as fa

        def loss(q, k, v):
            return fa.flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()

        sds = bf16(1, 1024, 2, 64)
        grad = jax.grad(loss, argnums=(0, 1, 2))
        with autotune.force_blocks(*blocks):
            models = trace_kernels(grad, sds, sds, sds)
            rep = analyze_kernels(grad, sds, sds, sds, name="flash")
        assert [len(m.grid) for m in models] == [2, 2]
        assert rep.errors == [] and rep.warnings == [], rep.to_text()

    def test_fused_ce_non_divisible_traced_clean(self):
        from paddle_tpu.ops.pallas.fused_ce import (
            fused_linear_cross_entropy)

        def loss(h, w, lab):
            return fused_linear_cross_entropy(h, w, lab).sum()

        rep = analyze_kernels(
            jax.grad(loss, argnums=(0, 1)), f32(300, 128),
            f32(1000, 128), jax.ShapeDtypeStruct((300,), jnp.int32),
            name="fused_ce")
        assert rep.errors == [] and rep.warnings == [], rep.to_text()


# ---------------------------------------------------------------------------
# runtime half: the differential oracle
# ---------------------------------------------------------------------------


class TestVerifyOracle:
    def test_boundary_corpus_deterministic(self):
        a = verify.boundary_corpus(128, 256)
        b = verify.boundary_corpus(128, 256)
        assert a == b
        assert len(a) == 10                     # 5 shapes x 2 dtypes
        # the last shape is a square of several tiles a side
        assert (a[4]["sq"], a[4]["sk"]) == (512, 512)
        # ... on which every candidate of the sweep runs the two-level
        # nest in both dtypes, the table's lopsided tiles included
        from paddle_tpu.ops.pallas import autotune
        from paddle_tpu.ops.pallas import flash_attention as fa
        for bq, bk in autotune.CANDIDATES:
            for case in verify.boundary_corpus(bq, bk)[4::5]:
                assert case["sq"] == case["sk"] <= 1024
                assert fa._two_level(case["sq"], case["sk"], case["d"],
                                     case["dtype"], bq, bk, False), case
        assert {c["dtype"] for c in a} == {"float32", "bfloat16"}
        assert all(c["sq"] >= 128 and c["sk"] >= 256 for c in a)

    def test_disarmed_invokes_nothing(self):
        assert not verify.armed()

        def boom(*a):
            raise AssertionError("disarmed oracle must not call this")

        assert verify.verify_call("k", boom, boom, (1,)) is None

    def test_armed_agreement(self):
        set_flags({"pallas_verify": True})
        x = jnp.arange(8.0)
        res = verify.verify_call("k", lambda v: v * 2, lambda v: v + v,
                                 (x,), out_labels=["k.out"])
        assert res is not None and not res.divergent
        assert res.checked == 1

    def test_armed_divergence_names_operand_and_legs(self):
        set_flags({"pallas_verify": True})
        before = monitor.get_stat("pallas_divergence_total")
        x = jnp.arange(8.0)
        res = verify.verify_call("k", lambda v: v * 2, lambda v: v * 3,
                                 (x,), out_labels=["k.out"])
        assert res is not None and res.divergent
        assert res.operand == "k.out"
        assert res.legs == ("compiled", "reference")
        assert monitor.get_stat("pallas_divergence_total") == before + 1
        from paddle_tpu.framework.observability import flight
        ev = flight.recent(4, kind="pallas.divergence")
        assert ev and ev[-1]["attrs"]["operand"] == "k.out"

    def test_chaos_swallow_counts_not_raises(self):
        set_flags({"pallas_verify": True})
        before = monitor.get_stat("pallas_verify_errors_total")
        x = jnp.arange(8.0)
        with chaos.inject("pallas.verify", mode="error", every=1):
            res = verify.verify_call("k", lambda v: v * 2,
                                     lambda v: v * 2, (x,),
                                     out_labels=["k.out"])
        assert res is None
        assert monitor.get_stat("pallas_verify_errors_total") == \
            before + 1

    def test_broken_oracle_reference_swallowed(self):
        set_flags({"pallas_verify": True})
        before = monitor.get_stat("pallas_verify_errors_total")

        def broken_ref(v):
            raise RuntimeError("reference leg is broken")

        res = verify.verify_call("k", lambda v: v * 2, broken_ref,
                                 (jnp.arange(4.0),),
                                 out_labels=["k.out"])
        assert res is None
        assert monitor.get_stat("pallas_verify_errors_total") == \
            before + 1

    def test_pallas_verify_in_fault_points(self):
        assert "pallas.verify" in chaos.FAULT_POINTS


# ---------------------------------------------------------------------------
# fixture-pinned acceptance: static and runtime name the SAME operand
# ---------------------------------------------------------------------------


def _load_fixture():
    spec = importlib.util.spec_from_file_location(
        "pallas_oob_fixture", FIXTURE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestFixtureAcceptance:
    def test_static_flags_601_603_on_fixture_out(self):
        mod = _load_fixture()
        rep = mod.pallas_report()
        assert len(rep.errors) >= 2
        rules = _rules(rep)
        assert "PTA601" in rules and "PTA603" in rules
        for d in rep.diagnostics:
            assert "fixture.out" in d.message

    def test_runtime_divergence_same_label(self):
        mod = _load_fixture()
        set_flags({"pallas_verify": True})
        res = mod.run()
        assert res is not None and res.divergent
        assert res.operand == "fixture.out"     # the static pass's label
        assert res.legs == ("interpret", "reference")

    def test_chaos_leg_swallows(self):
        mod = _load_fixture()
        set_flags({"pallas_verify": True})
        before = monitor.get_stat("pallas_verify_errors_total")
        assert mod.run(chaos_verify_error=True) is None
        assert monitor.get_stat("pallas_verify_errors_total") == \
            before + 1
