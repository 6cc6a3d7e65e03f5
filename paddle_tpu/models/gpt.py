"""GPT — the flagship decoder-only LM, built TPU-first.

Capability parity: the reference trains GPT-style transformers through
python/paddle/nn/layer/transformer.py (MultiHeadAttention :115,
TransformerDecoder) stacked as Python sublayers, with fused attention only
at inference (paddle/fluid/operators/fused/multihead_matmul_op.cu) and
pipeline/TP wired by program rewrite (fleet meta-optimizers).

TPU-native design decisions:
- **Stacked parameters + lax.scan over layers**: one (L, ...) tensor per
  weight kind instead of L separate sublayers.  XLA compiles ONE layer body
  regardless of depth (compile time O(1) in L), `jax.checkpoint` gives
  per-layer remat, and the leading L axis is exactly what pipeline
  parallelism shards over ``pp``.
- **DistAttr hybrid shardings** (dp×mp×pp×sp) declared on construction —
  the 4-D hybrid the reference reaches via sharding_optimizer.py:115-138,
  here just NamedShardings consumed by ShardedTrainStep.
- **Attention**: Pallas flash kernel on TPU (paddle_tpu/ops/pallas),
  ring attention over the ``sp`` axis for long context (capability the
  reference lacks, SURVEY.md §5.7), XLA softmax for shapes and backends
  the kernel's ``supported()`` gate rejects.
- Logits tied to the (mp-sharded) token embedding.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import Parameter, Tensor, apply1
from paddle_tpu.nn.layer.layers import Layer
from paddle_tpu.parallel.mesh import DistAttr, get_mesh
from paddle_tpu.profiler import CountedEvent

__all__ = ["GPTConfig", "GPT", "gpt_loss", "gpt_tiny", "gpt2_small",
           "gpt2_medium", "gpt2_345m"]


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=1024, num_layers=24,
                 num_heads=16, ffn_size: Optional[int] = None,
                 max_seq_len=1024, initializer_range=0.02,
                 remat: bool = True, n_microbatches: int = 1,
                 use_flash_attention: bool = True, seed: int = 0,
                 schedule_mode: int = 0, scan_unroll: int = 1):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.ffn_size = ffn_size or 4 * hidden_size
        self.max_seq_len = max_seq_len
        self.initializer_range = initializer_range
        self.remat = remat
        self.n_microbatches = n_microbatches
        self.use_flash_attention = use_flash_attention
        self.seed = seed
        # pipeline schedule under pp>1 (reference section_worker.cc:115
        # schedule_mode): 0 = F-then-B via autodiff, 1 = interleaved 1F1B
        # (O(P·mb) activation memory) — training loss must then go through
        # gpt_loss, which routes to the fused pipeline+loss program
        self.schedule_mode = schedule_mode
        # lax.scan unroll factor for the layer loop: 1 = compile-time
        # O(1) in depth (the default design point); num_layers = fully
        # unrolled, letting XLA schedule across layers and dropping the
        # scan-carry copies/dynamic-slices (measured: see bench notes)
        self.scan_unroll = scan_unroll

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


def gpt_tiny(**kw):
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_layers", 4)
    kw.setdefault("num_heads", 4)
    kw.setdefault("max_seq_len", 128)
    return GPTConfig(**kw)


def gpt2_small(**kw):
    return GPTConfig(hidden_size=768, num_layers=12, num_heads=12, **kw)


def gpt2_medium(**kw):
    return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16, **kw)


# "GPT-2 345M" — the BASELINE.md flagship config
gpt2_345m = gpt2_medium


# fixed parameter order for the pure forward
_PARAM_ORDER = ("wte", "wpe", "ln1_w", "ln1_b", "qkv_w", "qkv_b", "prj_w",
                "prj_b", "ln2_w", "ln2_b", "fc_w", "fc_b", "out_w", "out_b",
                "lnf_w", "lnf_b")


class GPT(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        with CountedEvent("model.init"):
            self._init_parameters(config)

    def _init_parameters(self, c: GPTConfig):
        """Every parameter, drawn on the host from ``c.seed``."""
        rng = np.random.default_rng(c.seed)
        std = c.initializer_range
        L, H, F, V, S = (c.num_layers, c.hidden_size, c.ffn_size,
                         c.vocab_size, c.max_seq_len)

        def norm(shape, scale=std):
            return rng.standard_normal(shape).astype(np.float32) * scale

        def param(name, value, spec=None):
            p = Parameter(value, name=f"gpt.{name}")
            if spec is not None:
                p.dist_attr = DistAttr(spec)
            self.add_parameter(name, p)
            return p

        param("wte", norm((V, H)), ("mp", None))
        param("wpe", norm((S, H)))
        param("ln1_w", np.ones((L, H), np.float32), ("pp",))
        param("ln1_b", np.zeros((L, H), np.float32), ("pp",))
        param("qkv_w", norm((L, H, 3 * H)), ("pp", None, "mp"))
        param("qkv_b", np.zeros((L, 3 * H), np.float32), ("pp", "mp"))
        # GPT-2 residual-projection scaling: std/sqrt(2L)
        param("prj_w", norm((L, H, H), std / math.sqrt(2 * L)),
              ("pp", "mp", None))
        param("prj_b", np.zeros((L, H), np.float32), ("pp",))
        param("ln2_w", np.ones((L, H), np.float32), ("pp",))
        param("ln2_b", np.zeros((L, H), np.float32), ("pp",))
        param("fc_w", norm((L, H, F)), ("pp", None, "mp"))
        param("fc_b", np.zeros((L, F), np.float32), ("pp", "mp"))
        param("out_w", norm((L, F, H), std / math.sqrt(2 * L)),
              ("pp", "mp", None))
        param("out_b", np.zeros((L, H), np.float32), ("pp",))
        param("lnf_w", np.ones((H,), np.float32))
        param("lnf_b", np.zeros((H,), np.float32))

    def forward(self, input_ids) -> Tensor:
        """input_ids (B, S) int -> logits (B, S, V)."""
        params = [self._parameters[n] for n in _PARAM_ORDER]
        fn = partial(_gpt_forward, self.config)
        return apply1(fn, *params, input_ids, name="gpt_forward")


def _ln(x, w, b, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _mark(x, *spec):
    # "sp" is intentionally excluded from activation constraints: the ring
    # attention shard_map's in_specs force the sequence sharding at the
    # boundary, and a with_sharding_constraint over sp in the backward pass
    # trips an XLA SPMD-partitioner check-failure (spmd_partitioner_util.h
    # IsScalarWithElementType) on CPU as of jax 0.9.
    from paddle_tpu.parallel.mesh import constrain
    return constrain(x, *spec, strip=("sp",))


def _attention(cfg: GPTConfig, q, k, v, manual_sp=False):
    """(B, S, nh, hd) causal attention; picks ring / flash / XLA.

    ``manual_sp``: the caller is already inside a shard_map whose manual
    set includes ``sp`` (the pipeline trunk) — run the ring attention
    body directly on the local sequence shard instead of opening a
    nested shard_map (sp×pp composition)."""
    mesh = get_mesh()
    scale = 1.0 / math.sqrt(cfg.head_dim)
    if manual_sp:
        from paddle_tpu.parallel.ring_attention import ring_attention_manual
        axes = tuple(a for a in ("dp", "pp", "sp")
                     if mesh.shape.get(a, 1) > 1)
        return ring_attention_manual(q, k, v, causal=True, scale=scale,
                                     n=mesh.shape["sp"], manual_axes=axes)
    if mesh.shape.get("sp", 1) > 1 and mesh.shape.get("pp", 1) == 1:
        # ring attention owns its shard_map region at the top level
        from paddle_tpu.parallel.ring_attention import ring_attention
        return ring_attention(q, k, v, causal=True, scale=scale, mesh=mesh)
    if cfg.use_flash_attention:
        # supported() decides from shapes and backend; what it accepts
        # runs in the kernel or raises — never quietly on the XLA path
        from paddle_tpu.ops.pallas import flash_attention as _fa
        from paddle_tpu.parallel.mesh import per_device
        if _fa.supported(tuple(q.shape), tuple(k.shape), True, causal=True):
            kernel = partial(_fa.flash_attention, causal=True, scale=scale)
            return per_device(kernel, mesh, (("dp", "sharding"), None,
                                             "mp", None))(q, k, v)
    from paddle_tpu.nn.functional.attention import _xla_attention
    return _xla_attention(q, k, v, None, scale, True)


def _make_stage(cfg: GPTConfig, manual_sp: bool):
    """Build the trunk stage function (scan over the stage's layer slice).
    Shared by forward (F-then-B) and the fused 1F1B loss program."""
    H, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim

    # the named scopes are the regions a device trace is read by
    # (profiler/__init__.py lists them); metadata only
    def layer(x, lp):
        b, s = x.shape[:2]   # local (microbatch) shape, not the global B,S
        with jax.named_scope("attn"):
            with jax.named_scope("ln"):
                h = _ln(x, lp["ln1_w"], lp["ln1_b"])
            with jax.named_scope("qkv"):
                qkv = h @ lp["qkv_w"] + lp["qkv_b"]           # (b,s,3H)
                qkv = _mark(qkv, "dp", "sp", "mp")
                q, k, v = jnp.split(qkv, 3, axis=-1)
                q = q.reshape(b, s, nh, hd)
                k = k.reshape(b, s, nh, hd)
                v = v.reshape(b, s, nh, hd)
            with jax.named_scope("core"):
                a = _attention(cfg, q, k, v,
                               manual_sp=manual_sp).reshape(b, s, H)
            with jax.named_scope("out"):
                x = x + a @ lp["prj_w"] + lp["prj_b"]
        with jax.named_scope("mlp"):
            with jax.named_scope("ln"):
                h2 = _ln(x, lp["ln2_w"], lp["ln2_b"])
            with jax.named_scope("up"):
                ff = jax.nn.gelu(h2 @ lp["fc_w"] + lp["fc_b"],
                                 approximate=True)
                ff = _mark(ff, "dp", "sp", "mp")
            with jax.named_scope("down"):
                x = x + ff @ lp["out_w"] + lp["out_b"]
        return _mark(x, "dp", "sp", None), None

    body = jax.checkpoint(layer) if cfg.remat else layer
    unroll = getattr(cfg, "scan_unroll", 1)

    def stage_fn(local_params, h):
        depth = jax.tree_util.tree_leaves(local_params)[0].shape[0]
        if unroll >= depth:
            # fully unrolled: static t[i] slices instead of lax.scan.  The
            # scan's stacked-grad dynamic-update-slice chain (measured
            # ~18 ms/step on GPT-2 345M) becomes static pads XLA fuses.
            for i in range(depth):
                lp = jax.tree_util.tree_map(lambda t: t[i], local_params)
                h, _ = body(h, lp)
            return h
        out, _ = jax.lax.scan(lambda carry, lp: body(carry, lp), h,
                              local_params, unroll=unroll)
        return out

    return stage_fn


def _stack_params(ln1_w, ln1_b, qkv_w, qkv_b, prj_w, prj_b, ln2_w, ln2_b,
                  fc_w, fc_b, out_w, out_b):
    return {"ln1_w": ln1_w, "ln1_b": ln1_b, "qkv_w": qkv_w,
            "qkv_b": qkv_b, "prj_w": prj_w, "prj_b": prj_b,
            "ln2_w": ln2_w, "ln2_b": ln2_b, "fc_w": fc_w, "fc_b": fc_b,
            "out_w": out_w, "out_b": out_b}


def _gpt_forward(cfg: GPTConfig, wte, wpe, ln1_w, ln1_b, qkv_w, qkv_b,
                 prj_w, prj_b, ln2_w, ln2_b, fc_w, fc_b, out_w, out_b,
                 lnf_w, lnf_b, ids, features_only: bool = False):
    mesh = get_mesh()
    B, S = ids.shape

    with jax.named_scope("embed"):
        x = wte[ids] + wpe[:S][None, :, :]
        x = _mark(x, "dp", "sp", None)

    stacked = _stack_params(ln1_w, ln1_b, qkv_w, qkv_b, prj_w, prj_b,
                            ln2_w, ln2_b, fc_w, fc_b, out_w, out_b)
    pp = mesh.shape.get("pp", 1)
    sp = mesh.shape.get("sp", 1)
    stage_fn = _make_stage(cfg, manual_sp=(pp > 1 and sp > 1))

    if pp > 1:
        from paddle_tpu.parallel.pipeline import pipeline_forward
        x = pipeline_forward(stage_fn, stacked, x,
                             n_microbatches=max(cfg.n_microbatches, pp),
                             mesh=mesh,
                             seq_axis="sp" if sp > 1 else None)
    else:
        x = stage_fn(stacked, x)

    with jax.named_scope("head_loss"):
        x = _ln(x, lnf_w, lnf_b)
        if features_only:
            return _mark(x, "dp", "sp", None)
        logits = x @ wte.T                             # tied head
        return _mark(logits, "dp", "sp", "mp")


def _gpt_1f1b_loss(cfg: GPTConfig, wte, wpe, ln1_w, ln1_b, qkv_w, qkv_b,
                   prj_w, prj_b, ln2_w, ln2_b, fc_w, fc_b, out_w, out_b,
                   lnf_w, lnf_b, ids, label_ids):
    """Fused pipeline+loss program under the 1F1B schedule: the head (final
    LN + tied logits + CE) runs on the LAST stage at B-time, which is what
    lets forward and backward interleave (reference section_worker.cc:115
    schedule_mode 1 with the loss section on the last device)."""
    from paddle_tpu.parallel.pipeline import make_pipeline_train_1f1b
    mesh = get_mesh()
    B, S = ids.shape
    pp = mesh.shape.get("pp", 1)
    sp = mesh.shape.get("sp", 1)

    with jax.named_scope("embed"):
        x = wte[ids] + wpe[:S][None, :, :]
        x = _mark(x, "dp", "sp", None)
    stacked = _stack_params(ln1_w, ln1_b, qkv_w, qkv_b, prj_w, prj_b,
                            ln2_w, ln2_b, fc_w, fc_b, out_w, out_b)
    stage_fn = _make_stage(cfg, manual_sp=(pp > 1 and sp > 1))
    head = {"wte": wte, "lnf_w": lnf_w, "lnf_b": lnf_b}

    # pre-shifted next-token labels with a -1 sentinel on the (global)
    # final position: the shift never crosses an sp shard boundary, and
    # the weight mask falls out of the sentinel
    labels = jnp.concatenate(
        [label_ids[:, 1:], jnp.full((B, 1), -1, label_ids.dtype)], axis=1)

    # memoize the built schedule per (config, mesh, seq-len): the builder
    # wraps a fresh jax.jit each time, so eager callers would otherwise
    # retrace/recompile every step
    key = (mesh, S, cfg.num_layers, cfg.hidden_size, cfg.num_heads,
           cfg.remat, cfg.use_flash_attention,
           max(cfg.n_microbatches, pp))
    loss_fn = _1F1B_CACHE.get(key)
    if loss_fn is None:
        if len(_1F1B_CACHE) > 16:   # bound the mesh/jit refs it pins
            _1F1B_CACHE.clear()
        @jax.named_scope("head_loss")
        def head_loss(hp, y, lab):
            # local-sum / GLOBAL-denominator (make_pipeline_train_1f1b's
            # sp contract): each sp shard sums its slice; the schedule
            # psums the shards
            h = _ln(y, hp["lnf_w"], hp["lnf_b"])
            lg = (h @ hp["wte"].T).astype(jnp.float32)
            w = (lab >= 0).astype(jnp.float32)
            tg = jnp.maximum(lab, 0)
            logz = jax.scipy.special.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, tg[..., None], axis=-1)[..., 0]
            return jnp.sum((logz - gold) * w) / (y.shape[0] * (S - 1))

        loss_fn = make_pipeline_train_1f1b(
            stage_fn, head_loss, max(cfg.n_microbatches, pp), mesh=mesh,
            seq_axis="sp" if sp > 1 else None)
        _1F1B_CACHE[key] = loss_fn
    return loss_fn(stacked, head, x, labels)


_1F1B_CACHE: dict = {}


def _gpt_fused_ce_loss(cfg: GPTConfig, *args):
    """Forward to the final LN, then blockwise Pallas linear+softmax-CE
    against the tied embedding — the (B, S, V) logits never reach HBM
    (reference fused-op tier role, operators/fused/ +
    softmax_with_cross_entropy_op.*)."""
    from paddle_tpu.ops.pallas.fused_ce import fused_linear_cross_entropy
    params, (ids, labels) = args[:-2], args[-2:]
    wte = params[0]
    B, S = ids.shape
    h = _gpt_forward(cfg, *params, ids, features_only=True)    # (B,S,H)
    with jax.named_scope("head_loss"):
        # next-token labels with a -1 sentinel on the final position
        # (same convention as the 1F1B head)
        lab = jnp.concatenate(
            [labels[:, 1:], jnp.full((B, 1), -1, labels.dtype)], axis=1)
        lab_flat = lab.reshape(B * S)
        loss_n = fused_linear_cross_entropy(
            h.reshape(B * S, h.shape[-1]), wte, lab_flat)
        w = (lab_flat >= 0).astype(jnp.float32)
        return jnp.sum(loss_n * w) / (B * (S - 1))


def _use_fused_ce() -> bool:
    from paddle_tpu.framework.flags import flag
    return bool(flag("gpt_fused_ce"))


def gpt_loss(model, input_ids, labels):
    """Causal-LM cross entropy (f32 softmax); labels == input tokens,
    shifted internally.  Under pp>1 with schedule_mode=1 the whole
    pipeline+loss runs as one interleaved 1F1B program.  On a single
    device with a TPU attached, the head+CE runs as the fused Pallas
    blockwise kernel (no (B, S, V) logits in HBM)."""
    from paddle_tpu.ops.pallas import fused_ce
    cfg = getattr(model, "config", None)
    mesh = get_mesh()
    if cfg is not None and getattr(cfg, "schedule_mode", 0) == 1 and \
            mesh.shape.get("pp", 1) > 1:
        params = [model._parameters[n] for n in _PARAM_ORDER]
        fn = partial(_gpt_1f1b_loss, cfg)
        return apply1(fn, *params, input_ids, labels,
                      name="gpt_loss_1f1b")
    B, S = input_ids.shape
    single_dev = math.prod(mesh.shape.values()) == 1
    if cfg is not None and single_dev and _use_fused_ce() and \
            fused_ce.supported(B * S, cfg.hidden_size):
        # fused head+CE needs the pre-head hiddens, so it takes the whole
        # forward as one pure fn (mesh-off fast path; under a mesh the
        # logits path keeps its mp sharding annotations).
        #
        # Opt-in (FLAGS_gpt_fused_ce): measured on v5e, XLA runs the
        # unfused head+CE at ~MXU peak (13 ms for the 3×845 GF passes at
        # B=8·S=1024·V=50k), so the kernel buys no time — what it buys is
        # the 1.65 GB (B,S,V) f32 logits buffer, lifting the max
        # no-remat batch from 8 to 12+.  Use it when HBM, not step time,
        # is the binding constraint.
        params = [model._parameters[n] for n in _PARAM_ORDER]
        fn = partial(_gpt_fused_ce_loss, cfg)
        return apply1(fn, *params, input_ids, labels,
                      name="gpt_loss_fused")
    logits = model(input_ids)

    @jax.named_scope("head_loss")
    def ce(logits, ids):
        lg = logits[:, :-1].astype(jnp.float32)
        tg = ids[:, 1:]
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, tg[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    return apply1(ce, logits, labels, name="gpt_loss")
