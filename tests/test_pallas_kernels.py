"""Pallas kernel tests.

On the CPU test mesh the TPU kernels can't execute natively; kernel
*logic* is validated via pallas interpret mode, and the dispatch gating
(supported()) plus the XLA fallback numerics are covered directly.  Real
chip timing/validation runs in ``tools/kernel_check.py`` and the benchmark.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attention as fa


def test_supported_gating_cpu():
    # CPU backend → kernel path off, XLA fallback on
    assert not fa.supported((2, 512, 4, 128), (2, 512, 4, 128), True)


def test_supported_shape_rules():
    # regardless of backend, bad shapes must be rejected
    assert not fa.supported((2, 100, 4, 128), (2, 100, 4, 128), True)
    assert not fa.supported((2, 512, 4, 100), (2, 512, 4, 100), True)
    assert not fa.supported((2, 512, 4, 128), (2, 512, 4, 128), False)


@pytest.mark.parametrize("causal", [True, False])
def test_xla_reference_matches_naive(causal):
    rng = np.random.default_rng(0)
    B, S, H, D = 2, 64, 2, 16
    q = jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
    scale = 1.0 / np.sqrt(D)
    out = fa._xla_reference(q, k, v, scale, causal)

    # naive per-head reference
    qh = np.asarray(q).transpose(0, 2, 1, 3)
    kh = np.asarray(k).transpose(0, 2, 1, 3)
    vh = np.asarray(v).transpose(0, 2, 1, 3)
    s = np.einsum("bhsd,bhtd->bhst", qh, kh) * scale
    if causal:
        mask = np.tril(np.ones((S, S), bool))
        s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    o = np.einsum("bhst,bhtd->bhsd", p, vh).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), o, rtol=2e-4, atol=2e-5)


class TestInterpretMode:
    """Kernel logic on CPU via pallas interpret mode — forward AND backward,
    including causal and cross-length (sq != sk) shapes (the round-1 causal
    mask convention bug would fail these)."""

    def setup_method(self):
        fa._INTERPRET = True
        # shrink blocks so the grids are multi-block: the cross-block
        # online-softmax rescale, scratch accumulate/finish revisits, and
        # the causal block-skip predicate all execute under test
        self._blocks = (fa.BLOCK_Q, fa.BLOCK_K)
        fa.BLOCK_Q = fa.BLOCK_K = 128

    def teardown_method(self):
        fa._INTERPRET = False
        fa.BLOCK_Q, fa.BLOCK_K = self._blocks

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("sq,sk", [(256, 256), (128, 256), (128, 384)])
    def test_forward_matches_xla(self, causal, sq, sk):
        rng = np.random.default_rng(0)
        B, H, D = 1, 2, 64
        q = jnp.asarray(rng.standard_normal((B, sq, H, D)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((B, sk, H, D)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((B, sk, H, D)).astype(np.float32))
        scale = 1.0 / np.sqrt(D)
        out, lse = fa._flash_fwd(q, k, v, None, None, None, scale, causal)
        ref = fa._xla_reference(q, k, v, scale, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("sq,sk", [(256, 256), (128, 256)])
    def test_backward_matches_xla(self, causal, sq, sk):
        rng = np.random.default_rng(1)
        B, H, D = 1, 2, 64
        q = jnp.asarray(rng.standard_normal((B, sq, H, D)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((B, sk, H, D)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((B, sk, H, D)).astype(np.float32))
        scale = 1.0 / np.sqrt(D)

        def loss_flash(q, k, v):
            return (fa.flash_attention(q, k, v, causal, scale) ** 2).sum()

        def loss_ref(q, k, v):
            return (fa._xla_reference(q, k, v, scale, causal) ** 2).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4,
                                       err_msg=f"d{name}")

    def test_supported_rejects_causal_more_queries(self):
        assert not fa.supported((1, 256, 2, 64), (1, 128, 2, 64), True,
                                causal=True)
        assert fa.supported((1, 128, 2, 64), (1, 256, 2, 64), True,
                            causal=True)

    def test_supported_mask_shapes(self):
        q = (2, 256, 4, 64)
        # canonical padding mask (B,1,1,Sk) rides the kernel now
        assert fa.supported(q, q, False, bias_shape=(2, 1, 1, 256))
        assert fa.supported(q, q, False, bias_shape=(1, 4, 256, 256))
        assert fa.supported(q, q, False, bias_shape=(2, 4, 256, 256))
        assert fa.supported(q, q, False, bias_shape=(256,))
        # key dim must be full; odd broadcast extents rejected
        assert not fa.supported(q, q, False, bias_shape=(2, 1, 1, 128))
        assert not fa.supported(q, q, False, bias_shape=(3, 1, 1, 256))
        # mask present but inexpressible → XLA path
        assert not fa.supported(q, q, False)
        # segments alone are fine
        assert fa.supported(q, q, False, segments=True)


def _rand_qkv(rng, b, sq, sk, h, d, dtype=np.float32):
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)).astype(dtype))
    k = jnp.asarray(rng.standard_normal((b, sk, h, d)).astype(dtype))
    v = jnp.asarray(rng.standard_normal((b, sk, h, d)).astype(dtype))
    return q, k, v


class TestMaskedInterpret:
    """Masked kernel paths (bias tiles, segment ids, dbias) in interpret
    mode — parity vs the XLA reference, forward and backward."""

    def setup_method(self):
        fa._INTERPRET = True
        self._blocks = (fa.BLOCK_Q, fa.BLOCK_K)
        fa.BLOCK_Q = fa.BLOCK_K = 128

    def teardown_method(self):
        fa._INTERPRET = False
        fa.BLOCK_Q, fa.BLOCK_K = self._blocks

    # the two middle shapes are covered on both sides (per-batch and
    # per-head-and-query broadcast, then no broadcast): the full
    # (2, 2, 256, 256) bias is the heaviest pair of the file and slow
    @pytest.mark.parametrize("bias_shape", [
        (2, 1, 1, 256), (1, 2, 256, 256),
        pytest.param((2, 2, 256, 256), marks=pytest.mark.slow),
        (1, 1, 1, 256)])
    @pytest.mark.parametrize("causal", [False, True])
    def test_bias_forward_backward(self, bias_shape, causal):
        rng = np.random.default_rng(3)
        B, S, H, D = 2, 256, 2, 64
        q, k, v = _rand_qkv(rng, B, S, S, H, D)
        bias = jnp.asarray(
            rng.standard_normal(bias_shape).astype(np.float32))
        scale = 1.0 / np.sqrt(D)

        def loss_flash(q, k, v, bias):
            return (fa.flash_attention(q, k, v, causal=causal, scale=scale,
                                       bias=bias) ** 2).sum()

        def loss_ref(q, k, v, bias):
            return (fa._xla_reference(q, k, v, scale, causal,
                                      bias=bias) ** 2).sum()

        np.testing.assert_allclose(
            np.asarray(loss_flash(q, k, v, bias)),
            np.asarray(loss_ref(q, k, v, bias)), rtol=2e-4)
        gf = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, bias)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b, name in zip(gf, gr, ["dq", "dk", "dv", "dbias"]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4, err_msg=name)

    def test_padding_bool_mask_matches_xla(self):
        """(B,1,1,Sk) bool padding mask built from per-sample lengths —
        the standard padded-batch BERT layout."""
        rng = np.random.default_rng(4)
        B, S, H, D = 2, 256, 2, 64
        q, k, v = _rand_qkv(rng, B, S, S, H, D)
        lens = np.array([200, 131])
        mask = jnp.asarray(np.arange(S)[None, :] < lens[:, None]
                           ).reshape(B, 1, 1, S)
        scale = 1.0 / np.sqrt(D)
        out = fa.flash_attention(q, k, v, scale=scale, bias=mask)
        ref = fa._xla_reference(q, k, v, scale, False,
                                bias=jnp.where(mask, 0.0, -1e30))
        # compare only valid query rows (padded queries attend nothing in
        # the kernel semantic; XLA's -1e30 clamp makes them uniform)
        for bi, ln in enumerate(lens):
            np.testing.assert_allclose(np.asarray(out)[bi, :ln],
                                       np.asarray(ref)[bi, :ln],
                                       rtol=2e-4, atol=2e-5)

    def test_fully_masked_rows_zero(self):
        rng = np.random.default_rng(5)
        B, S, H, D = 1, 256, 1, 64
        q, k, v = _rand_qkv(rng, B, S, S, H, D)
        mask = jnp.zeros((B, 1, 1, S), dtype=bool).at[:, :, :, :5].set(True)
        out = fa.flash_attention(q, k, v, bias=mask)
        # valid rows finite; the mask only hides keys, so all query rows
        # see 5 keys — but a row-hiding mask zeroes outputs:
        rowmask = jnp.zeros((B, 1, S, S), dtype=bool)
        out2 = fa.flash_attention(q, k, v, bias=rowmask)
        assert np.all(np.asarray(out2) == 0.0)
        assert np.all(np.isfinite(np.asarray(out)))

    @pytest.mark.parametrize("causal", [False, True])
    def test_segment_ids(self, causal):
        """Packed sequences: parity vs XLA with the materialised mask."""
        rng = np.random.default_rng(6)
        B, S, H, D = 2, 256, 2, 64
        q, k, v = _rand_qkv(rng, B, S, S, H, D)
        segs = np.repeat(np.arange(4), 64)[None, :].repeat(B, 0)
        segs = jnp.asarray(segs.astype(np.int32))
        scale = 1.0 / np.sqrt(D)

        def loss_flash(q, k, v):
            return (fa.flash_attention(q, k, v, causal=causal, scale=scale,
                                       q_segment_ids=segs,
                                       kv_segment_ids=segs) ** 2).sum()

        def loss_ref(q, k, v):
            return (fa._xla_reference(q, k, v, scale, causal, q_seg=segs,
                                      kv_seg=segs) ** 2).sum()

        np.testing.assert_allclose(np.asarray(loss_flash(q, k, v)),
                                   np.asarray(loss_ref(q, k, v)), rtol=2e-4)
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4,
                                       err_msg=f"d{name}")

    def test_bias_bf16(self):
        rng = np.random.default_rng(7)
        B, S, H, D = 1, 256, 2, 64
        q, k, v = _rand_qkv(rng, B, S, S, H, D)
        q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
        bias = jnp.asarray(rng.standard_normal((B, 1, 1, S))
                           .astype(np.float32))
        out = fa.flash_attention(q, k, v, bias=bias)
        ref = fa._xla_reference(q.astype(jnp.float32),
                                k.astype(jnp.float32),
                                v.astype(jnp.float32),
                                1.0 / np.sqrt(D), False, bias=bias)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                                   np.asarray(ref), rtol=3e-2, atol=3e-2)

    def test_sdpa_routes_mask_to_kernel(self):
        """nn.functional.scaled_dot_product_attention with a mask must hit
        the kernel path (not the O(S²) fallback) when shapes allow."""
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        rng = np.random.default_rng(8)
        B, S, H, D = 2, 256, 2, 64
        q = paddle.to_tensor(rng.standard_normal((B, S, H, D))
                             .astype(np.float32))
        kk = paddle.to_tensor(rng.standard_normal((B, S, H, D))
                              .astype(np.float32))
        vv = paddle.to_tensor(rng.standard_normal((B, S, H, D))
                              .astype(np.float32))
        mask = paddle.to_tensor(
            (np.arange(S)[None, :] < 200).reshape(1, 1, 1, S))
        calls = []
        orig = fa.flash_attention

        def spy(*a, **kw):
            calls.append(kw)
            return orig(*a, **kw)
        fa.flash_attention = spy
        try:
            out = F.scaled_dot_product_attention(q, kk, vv, attn_mask=mask)
        finally:
            fa.flash_attention = orig
        assert calls, "masked sdpa fell back to the XLA path"
        assert calls[0].get("bias_grad") is False
        ref = fa._xla_reference(
            q._data, kk._data, vv._data, 1.0 / np.sqrt(D), False,
            bias=jnp.where(mask._data, 0.0, -1e30))
        np.testing.assert_allclose(np.asarray(out._data),
                                   np.asarray(ref), rtol=2e-4, atol=2e-5)

    def test_functional_flash_attention_segments(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        rng = np.random.default_rng(9)
        B, S, H, D = 1, 256, 2, 64
        q = paddle.to_tensor(rng.standard_normal((B, S, H, D))
                             .astype(np.float32))
        segs = paddle.to_tensor(
            np.repeat(np.arange(2), 128)[None, :].astype(np.int32))
        out = F.flash_attention(q, q, q, causal=True, q_segment_ids=segs,
                                kv_segment_ids=segs)
        ref = fa._xla_reference(q._data, q._data, q._data,
                                1.0 / np.sqrt(D), True,
                                q_seg=segs._data, kv_seg=segs._data)
        np.testing.assert_allclose(np.asarray(out._data), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


class TestNonDivisibleTails:
    """Non-divisible sequence lengths ride cdiv grids with tail-masked
    blocks (the PTA601/PTA604 invariants) — pinned against the XLA
    reference so a regressed mask shows up as a numeric diff, exactly
    what the ops/pallas/verify.py oracle checks at runtime."""

    def setup_method(self):
        fa._INTERPRET = True
        self._saved = (fa.BLOCK_Q, fa.BLOCK_K, fa._MIN_BLOCK)
        # small blocks so the tail blocks are multi-block at test sizes
        fa.BLOCK_Q = fa.BLOCK_K = 128
        fa._MIN_BLOCK = 32

    def teardown_method(self):
        fa._INTERPRET = False
        fa.BLOCK_Q, fa.BLOCK_K, fa._MIN_BLOCK = self._saved

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("sq,sk", [(80, 80), (80, 112), (200, 200),
                                       (130, 260)])
    def test_forward_tail_matches_xla(self, causal, sq, sk):
        rng = np.random.default_rng(3)
        B, H, D = 1, 2, 64
        q = jnp.asarray(rng.standard_normal((B, sq, H, D)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((B, sk, H, D)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((B, sk, H, D)).astype(np.float32))
        scale = 1.0 / np.sqrt(D)
        assert fa.supported(q.shape, k.shape, True, causal=causal)
        out, _ = fa._flash_fwd(q, k, v, None, None, None, scale, causal)
        ref = fa._xla_reference(q, k, v, scale, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("sq,sk", [
        (80, 112), pytest.param(200, 200, marks=pytest.mark.slow)])
    def test_backward_tail_matches_xla(self, causal, sq, sk):
        rng = np.random.default_rng(4)
        B, H, D = 1, 2, 64
        q = jnp.asarray(rng.standard_normal((B, sq, H, D)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((B, sk, H, D)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((B, sk, H, D)).astype(np.float32))
        scale = 1.0 / np.sqrt(D)

        def loss_flash(q, k, v):
            return (fa.flash_attention(q, k, v, causal, scale) ** 2).sum()

        def loss_ref(q, k, v):
            return (fa._xla_reference(q, k, v, scale, causal) ** 2).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4,
                                       err_msg=f"d{name}")

    def test_masked_paths_keep_divisibility_gate(self):
        # bias/segment tiles are not tail-masked: non-divisible shapes
        # with a mask must keep falling back to XLA
        assert not fa.supported((1, 200, 2, 64), (1, 200, 2, 64), True,
                                bias_shape=(1, 1, 200, 200))
        assert not fa.supported((1, 200, 2, 64), (1, 200, 2, 64), True,
                                segments=True)


def _kernel_grids(fn, *args):
    """The grid of every pallas_call that tracing ``fn(*args)`` reaches."""
    from paddle_tpu.framework.analysis.pallas_kernels import trace_kernels
    return [tuple(m.grid) for m in trace_kernels(fn, *args)]


class TestTwoLevelTiling:
    """The two-level nest (resident K/V or Q/dO, an in-kernel loop over
    sub-tiles to the diagonal) in interpret mode: the unmasked body under
    the diagonal, the masked body on it and the loop bounds all execute,
    and calls outside its class keep the three-axis grid."""

    def setup_method(self):
        fa._INTERPRET = True

    def teardown_method(self):
        fa._INTERPRET = False

    @pytest.mark.parametrize("direction", ["forward", "backward"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    # (256, 128): a piece of keys skips the q block's rows before it (the
    # forward and dq update part of their accumulators); (128, 256): a
    # piece of queries skips the kv block's columns after it (dk/dv)
    @pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256)])
    @pytest.mark.parametrize("s", [256, 512])
    def test_causal_matches_xla(self, s, blocks, dtype, direction):
        from paddle_tpu.ops.pallas import autotune
        rng = np.random.default_rng(10)
        B, H, D = 1, 2, 64
        q, k, v = (x.astype(dtype) for x in _rand_qkv(rng, B, s, s, H, D))
        scale = 1.0 / np.sqrt(D)
        f32 = lambda x: np.asarray(x, dtype=np.float32)

        def loss(attn):
            return lambda q, k, v: (attn(q, k, v).astype(jnp.float32)
                                    ** 2).sum()

        flash = lambda q, k, v: fa.flash_attention(q, k, v, True, scale)
        ref = lambda q, k, v: fa._xla_reference(q, k, v, scale, True)
        if direction == "forward":
            run = lambda attn: (attn(q, k, v),)
            tol = dict(rtol=2e-4, atol=2e-5) if dtype == "float32" \
                else dict(rtol=3e-2, atol=3e-2)
        else:
            run = lambda attn: jax.grad(loss(attn), argnums=(0, 1, 2))(
                q, k, v)
            tol = dict(rtol=5e-3, atol=5e-4) if dtype == "float32" \
                else dict(rtol=5e-2, atol=1e-1)
        with autotune.force_blocks(*blocks):
            grids = _kernel_grids(lambda: run(flash))
            got = run(flash)
        n = 1 if direction == "forward" else 3
        assert len(grids) == n and all(len(g) == 2 for g in grids), grids
        # the float32 reference on the same (possibly bf16-rounded) inputs
        want = run(lambda q, k, v: ref(q.astype(jnp.float32),
                                       k.astype(jnp.float32),
                                       v.astype(jnp.float32)))
        for a, b, name in zip(got, want, ("out",) if n == 1 else "qkv"):
            assert a.dtype == jnp.dtype(dtype)
            np.testing.assert_allclose(f32(a), f32(b), err_msg=name, **tol)

    def test_a_model_of_many_layers_traces_each_kernel_once(self,
                                                           monkeypatch):
        """The nest's ``pallas_call``s go through jax's trace cache: the
        kernel bodies, a straight-line loop in every branch, are traced
        once however many layers call them, and every call still lands in
        the jaxpr and in the counters."""
        from paddle_tpu.framework import monitor
        from paddle_tpu.ops.pallas import autotune
        traced = []
        for name in ("_fwd_resident_kernel", "_bwd_dq_resident_kernel",
                     "_bwd_dkv_resident_kernel"):
            def body(*a, _real=getattr(fa, name), _name=name, **k):
                traced.append(_name)
                return _real(*a, **k)
            monkeypatch.setattr(fa, name, body)

        def loss(q, k, v):
            for _ in range(3):                       # three "layers"
                q = fa.flash_attention(q, k, v, causal=True)
            return q.astype(jnp.float32).sum()

        x = jax.ShapeDtypeStruct((1, 384, 3, 64), jnp.float32)  # 3 x 3 tiles
        before = monitor.get_stat("flash_subtiles_computed_total")
        with autotune.force_blocks(128, 128):
            text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
                x, x, x))
        assert sorted(traced) == ["_bwd_dkv_resident_kernel",
                                  "_bwd_dq_resident_kernel",
                                  "_fwd_resident_kernel"]
        assert [text.count(f"name={n}") for n in (
            "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")] == [3, 3, 3]
        # 6 of 9 sub-tiles a head a kernel, three kernels, three layers
        assert monitor.get_stat("flash_subtiles_computed_total") - before \
            == 6 * 3 * 3 * 3

    @pytest.mark.parametrize("kind", ["bias", "segments", "tail",
                                      "cross_length"])
    def test_other_calls_keep_the_three_axis_grid(self, kind):
        from paddle_tpu.ops.pallas import autotune
        rng = np.random.default_rng(11)
        B, H, D = 1, 2, 64
        sq, sk = {"tail": (320, 320), "cross_length": (128, 256)}.get(
            kind, (256, 256))
        q, k, v = _rand_qkv(rng, B, sq, sk, H, D)
        scale = 1.0 / np.sqrt(D)
        bias = jnp.asarray(rng.standard_normal((B, 1, 1, sk))
                           .astype(np.float32)) if kind == "bias" else None
        segs = jnp.asarray(np.repeat(np.arange(2), sq // 2)[None, :]
                           .astype(np.int32)) if kind == "segments" else None

        def loss(attn):
            return lambda q, k, v: (attn(q, k, v) ** 2).sum()

        flash = lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, scale=scale, bias=bias,
            q_segment_ids=segs, kv_segment_ids=segs)
        ref = lambda q, k, v: fa._xla_reference(
            q, k, v, scale, True, bias=bias, q_seg=segs, kv_seg=segs)
        with autotune.force_blocks(128, 128):
            grids = _kernel_grids(jax.grad(loss(flash), argnums=(0, 1, 2)),
                                  q, k, v)
            gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
        # (a bias adds its dbias kernel, on four axes)
        assert [len(g) for g in grids][:3] == [3, 3, 3] and \
            2 not in map(len, grids), grids
        gr = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4,
                                       err_msg=f"d{name}")

    def test_operands_over_the_vmem_budget_keep_the_three_axis_grid(self):
        assert fa._two_level(1024, 1024, 64, jnp.bfloat16, 256, 256, False)
        assert fa._two_level(1024, 1024, 128, jnp.float32, 512, 512, False)
        assert not fa._two_level(2048, 2048, 64, jnp.bfloat16, 256, 256,
                                 False)
        assert not fa._two_level(1024, 1024, 64, jnp.bfloat16, 256, 256,
                                 True)

    def test_subtile_counters_at_the_gpt2_shape(self):
        """(S, t) = (1024, 256): 10 of a head's 16 sub-tiles are computed
        and 6 left out, in each of the three kernels — counts taken when
        the call is traced."""
        from paddle_tpu.framework import monitor
        from paddle_tpu.ops.pallas import autotune
        B, S, H, D = 2, 1024, 3, 64
        x = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16)

        def traced(grad=False):
            """(computed, skipped) a head that tracing one call adds.  A
            new function each time: jax caches a trace by the function."""
            loss = lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True).astype(jnp.float32).sum()
            names = ("flash_subtiles_computed_total",
                     "flash_subtiles_skipped_total")
            before = [monitor.get_stat(n) for n in names]
            jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)) if grad
                           else loss, x, x, x)
            return tuple((monitor.get_stat(n) - b) / (B * H)
                         for n, b in zip(names, before))

        with autotune.force_blocks(256, 256):
            assert traced() == (10, 6)
            computed, skipped = traced(grad=True)    # forward, dq, dk/dv
        assert (computed, skipped) == (30, 18)
        assert skipped / (computed + skipped) == 0.375
        # one (1024, 1024) tile, the parent's table entry, skips nothing
        with autotune.force_blocks(1024, 1024):
            assert traced() == (1, 0)
        # tiles that are not square count in squares of their shorter side,
        # so the share is of the area: a (1024, 256) dq kernel computes the
        # trapezoid under the diagonal, a (128, 1024) dk/dv kernel 36 of 64
        with autotune.force_blocks(512, 512, direction="fwd"), \
                autotune.force_blocks(1024, 256, direction="bwd"), \
                autotune.force_blocks(128, 1024, direction="dkv"):
            assert traced() == (3, 1)
            assert traced(grad=True) == (3 + 10 + 36, 1 + 6 + 28)
        text = monitor.export_prometheus()
        assert "above the causal diagonal" in text


def _parent_faster(sq, sk, causal):
    """The speed gate as it stood before PR 31: every non-causal call
    under 1024 x 1024 to XLA, whatever else it shows."""
    return causal or sq >= 1024 or sk >= 1024


# (what the call is, q shape, k shape, supported()'s other arguments, the
# answer).  The first block is what PR 31's in-model sweep measured (BERT-
# base, 16,384 tokens a step, one v5e chip; PERF.md section 6); every other
# call answers as before it.
_BERT = lambda s: (16384 // s, s, 12, 64)
_GATE_CASES = [
    ("bert_s512_full", _BERT(512), _BERT(512), {}, True),
    ("bert_s128_full", _BERT(128), _BERT(128), {}, False),
    ("bert_s256_full", _BERT(256), _BERT(256), {}, False),
    ("bert_s384_full", _BERT(384), _BERT(384), {}, False),
    ("bert_s768_full", _BERT(768), _BERT(768), {}, False),
    ("full_s1024", (8, 1024, 16, 64), (8, 1024, 16, 64), {}, True),
    ("gpt2_causal_s1024", (8, 1024, 16, 64), (8, 1024, 16, 64),
     {"causal": True}, True),
    ("hybrid_causal_s4096_d128", (1, 4096, 4, 128), (1, 4096, 4, 128),
     {"causal": True}, True),
    ("causal_s512", (4, 512, 4, 64), (4, 512, 4, 64), {"causal": True},
     True),
    ("padding_bias_s512", (32, 512, 12, 64), (32, 512, 12, 64),
     {"no_mask": False, "bias_shape": (32, 1, 1, 512)}, False),
    ("padding_bias_s2048", (2, 2048, 12, 64), (2, 2048, 12, 64),
     {"no_mask": False, "bias_shape": (2, 1, 1, 2048)}, True),
    ("segments_s512", (32, 512, 12, 64), (32, 512, 12, 64),
     {"no_mask": False, "segments": True}, False),
    ("vit_tail_s197", (64, 197, 12, 64), (64, 197, 12, 64), {}, False),
    ("tail_s500", (8, 500, 12, 64), (8, 500, 12, 64), {}, False),
    ("cross_256_512", (8, 256, 12, 64), (8, 512, 12, 64), {}, False),
    ("cross_512_2048", (8, 512, 12, 64), (8, 2048, 12, 64), {}, True),
    ("d128_s512_full", (8, 512, 8, 128), (8, 512, 8, 128), {}, False),
]


class TestDispatchGate:
    """``supported()`` on the chip as a pure function of the call's shape
    and mask class: ``backend_is_tpu`` patched to true, interpret mode
    off.  The capability half is covered above; here the speed half."""

    @pytest.fixture(autouse=True)
    def on_the_chip(self, monkeypatch):
        monkeypatch.setattr(fa, "backend_is_tpu", lambda: True)
        monkeypatch.setattr(fa, "_INTERPRET", False)

    @pytest.mark.parametrize("q,k,kw,want", [c[1:] for c in _GATE_CASES],
                             ids=[c[0] for c in _GATE_CASES])
    def test_the_answer_at_a_shape(self, q, k, kw, want):
        assert fa.supported(q, k, **kw) is want

    def test_calls_the_sweep_did_not_cover_answer_as_the_parent(self):
        """Every capable call outside (non-causal, no mask, square, d = 64,
        S a multiple of 128 under 1024) answers as before PR 31."""
        import itertools
        for s_q, s_k, d, causal, mask in itertools.product(
                (128, 197, 256, 320, 384, 512, 640, 768, 896, 1024, 2048),
                (128, 256, 512, 768, 1024, 2048), (64, 128), (False, True),
                ("none", "bias", "segments")):
            q, k = (2, s_q, 4, d), (2, s_k, 4, d)
            args = (mask == "none", causal,
                    (1, 1, 1, s_k) if mask == "bias" else None,
                    mask == "segments")
            swept = not causal and mask == "none" and s_q == s_k \
                and d == 64 and s_q % 128 == 0
            if not fa._capable(q, k, *args):
                assert not fa.supported(q, k, *args)
            elif not swept:
                assert fa.supported(q, k, *args) == \
                    _parent_faster(s_q, s_k, causal), (q, k, args)

    def test_the_gate_reads_shapes_only(self):
        """No flag, environment variable or batch size moves the answer."""
        import inspect
        source = inspect.getsource(fa._faster_than_xla)
        assert "environ" not in source and "get_flag" not in source
        assert list(inspect.signature(fa._faster_than_xla).parameters) == [
            "sq", "sk", "d", "causal", "masked"]
        assert list(inspect.signature(fa.supported).parameters) == [
            "q_shape", "k_shape", "no_mask", "causal", "bias_shape",
            "segments"]
        for batch in (1, 32, 1024):
            assert fa.supported((batch, 512, 12, 64), (batch, 512, 12, 64))

    def test_counters_move_once_per_traced_decision(self):
        from paddle_tpu.framework import monitor
        names = ("flash_dispatch_kernel_total",
                 "flash_dispatch_xla_for_speed_total")
        read = lambda: tuple(monitor.get_stat(n) for n in names)

        @jax.jit
        def layer(x):
            # what a model does while it is traced
            fa.supported(x.shape, x.shape)
            return x + 1

        before = read()
        x512 = jnp.zeros((2, 512, 2, 64), jnp.bfloat16)
        layer(x512), layer(x512)              # traced once, run twice
        assert read() == (before[0] + 1, before[1])
        layer(jnp.zeros((2, 128, 2, 64), jnp.bfloat16))
        assert read() == (before[0] + 1, before[1] + 1)
        # a call the kernels cannot compute is no decision on speed
        assert not fa.supported((2, 512, 2, 100), (2, 512, 2, 100))
        assert not fa.supported((2, 64, 2, 64), (2, 64, 2, 64))
        assert read() == (before[0] + 1, before[1] + 1)
        text = monitor.export_prometheus()
        assert "left to XLA because it is faster" in text


class TestBertCellGeometry:
    """The non-causal two-level nest at the tiles ``flash_blocks.json``
    ships for BERT-base's call at S = 512 (``512x512:d64:bfloat16:full:
    nobias`` and its ``:bwd`` / ``:dkv`` entries), a few heads, interpret
    mode: forward and dq / dk / dv against the float32 reference."""

    def setup_method(self):
        fa._INTERPRET = True

    def teardown_method(self):
        fa._INTERPRET = False

    def test_the_table_has_verified_tiles_for_the_call(self):
        from paddle_tpu.ops.pallas import autotune
        table = autotune._load()
        for suffix in ("", ":bwd", ":dkv"):
            entry = table["512x512:d64:bfloat16:full:nobias" + suffix]
            assert entry["verified"] is True
            bq, bk = entry["blocks"]
            assert 512 % bq == 0 and 512 % bk == 0
            assert fa._two_level(512, 512, 64, jnp.bfloat16, bq, bk, False)

    @pytest.mark.parametrize("direction", ["forward", "backward"])
    def test_matches_the_reference_at_the_shipped_tiles(self, direction):
        rng = np.random.default_rng(31)
        B, S, H, D = 1, 512, 3, 64
        q, k, v = (x.astype(jnp.bfloat16)
                   for x in _rand_qkv(rng, B, S, S, H, D))
        scale = 1.0 / np.sqrt(D)
        f32 = lambda x: np.asarray(x, dtype=np.float32)
        flash = lambda q, k, v: fa.flash_attention(q, k, v, False, scale)
        ref = lambda q, k, v: fa._xla_reference(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), scale, False)
        loss = lambda attn: lambda q, k, v: (
            attn(q, k, v).astype(jnp.float32) ** 2).sum()
        if direction == "forward":
            run = lambda attn: (attn(q, k, v),)
            tol = dict(rtol=3e-2, atol=3e-2)
        else:
            run = lambda attn: jax.grad(loss(attn), argnums=(0, 1, 2))(
                q, k, v)
            tol = dict(rtol=5e-2, atol=1e-1)
        grids = _kernel_grids(lambda: run(flash))
        n = 1 if direction == "forward" else 3
        # the nest: two grid axes, (batch x heads, blocks of the table)
        assert len(grids) == n and all(len(g) == 2 for g in grids), grids
        for a, b, name in zip(run(flash), run(ref),
                              ("out",) if n == 1 else "qkv"):
            np.testing.assert_allclose(f32(a), f32(b), err_msg=name, **tol)
