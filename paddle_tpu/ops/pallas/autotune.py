"""Measured flash-attention block-size cache.

Round-2 verdict item: BLOCK_Q/K=512 was a config-global compromise (the
256<->512 flip-flop in history shows the answer is shape-dependent).
This cache keys measured winners on (Sq, Sk, head_dim, dtype, causal,
biased) and, for the backward kernels, the direction.

An entry is **the tile a kernel computes at once**, (block_q, block_k).
What else it fixes depends on the loop nest ``flash_attention`` picks for
the call: on the three-axis grid it is also what one grid step fetches,
so a smaller tile buys finer causal skipping with more grid steps; on
the two-level nest (operands of a (batch, head) resident in VMEM) one of
the two is the block a grid step owns and the other the most a sub-tile
of the in-kernel loop takes of the resident axis, nothing is fetched per
sub-tile, and a causal mask clips each sub-tile to what it leaves
visible.  An entry measured under one nest says nothing about the other.

- ``flash_blocks.json`` next to this file ships pre-measured entries for
  the bench/model configs (regenerate with ``tools/flash_autotune.py``
  on a real chip).
- On a cache miss the kernel uses the BLOCK_Q/BLOCK_K heuristic, unless
  ``FLAGS_flash_autotune`` is set — then candidates are timed on-device
  once (fwd+bwd, value-fetch fenced) and the winner is persisted.

Reference role: the reference hand-tuned per-arch tile sizes inside its
CUDA kernels; on TPU the tile choice is a trace-time knob, so it can be
measured instead of guessed.
"""
from __future__ import annotations

import json
import os
import threading
_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "flash_blocks.json")
_cache = None
_lock = threading.Lock()

# set via force_blocks() during measurement; keys "both"/"fwd"/"bwd"/"dkv"
_FORCE: dict = {}

# the lopsided ones are for the two-level nest: dq wants a large block of
# queries against small pieces of keys, dk/dv the mirror image
CANDIDATES = [(128, 128), (256, 128), (128, 256), (256, 256), (512, 128),
              (256, 512), (512, 256), (512, 512), (1024, 128), (128, 1024),
              (1024, 256), (256, 1024), (1024, 512), (512, 1024),
              (1024, 1024)]


def _load() -> dict:
    global _cache
    if _cache is None:
        with _lock:
            if _cache is None:
                try:
                    with open(_PATH) as f:
                        _cache = json.load(f)
                except Exception:
                    _cache = {}
    return _cache


def _key(sq, sk, d, dtype, causal, biased, direction="fwd") -> str:
    base = (f"{sq}x{sk}:d{d}:{dtype}:"
            f"{'causal' if causal else 'full'}:"
            f"{'bias' if biased else 'nobias'}")
    # fwd keeps the historical key so shipped flash_blocks.json entries
    # stay valid; bwd and dkv entries are suffixed
    return base if direction == "fwd" else base + ":" + direction


def _entry_blocks(hit):
    """Entry value → (bq, bk).  Entries are either the legacy bare
    ``[bq, bk]`` list or the stamped ``{"blocks": [...], "verified":
    true}`` dict written when the differential oracle validated the
    candidate before it was timed."""
    if isinstance(hit, dict):
        hit = hit.get("blocks")
    return tuple(hit) if hit else None


# which pinned tile, then which table entry, a direction reads.  "dkv" is
# the dk/dv kernel on the two-level nest, where its tile is (the piece of
# queries a sub-tile takes, the block of keys a grid step owns) and so the
# mirror image of the dq kernel's: one "bwd" entry cannot suit both.
# Without an entry of its own it shares the backward's, the backward the
# forward's.
_FORCED_BY = {"fwd": ("fwd", "both"), "bwd": ("bwd", "both"),
              "dkv": ("dkv", "bwd", "both")}
_ENTRY_OF = {"fwd": ("fwd",), "bwd": ("bwd", "fwd"),
             "dkv": ("dkv", "bwd", "fwd")}


def lookup(sq, sk, d, dtype, causal, biased, direction="fwd"):
    for pin in _FORCED_BY[direction]:
        if pin in _FORCE:
            return _FORCE[pin]
    c = _load()
    for entry in _ENTRY_OF[direction]:
        hit = c.get(_key(sq, sk, d, str(dtype), causal, biased, entry))
        if hit is not None:
            return _entry_blocks(hit)
    return None


def record(sq, sk, d, dtype, causal, biased, blocks, persist=True,
           direction="fwd", verified=False):
    c = _load()
    entry = {"blocks": list(blocks), "verified": True} if verified \
        else list(blocks)
    c[_key(sq, sk, d, str(dtype), causal, biased, direction)] = entry
    if persist:
        try:
            with _lock, open(_PATH, "w") as f:
                json.dump(c, f, indent=1, sort_keys=True)
        except OSError:
            pass                       # read-only install: in-memory only


class force_blocks:
    """Context manager pinning the kernel block choice (measurement).
    ``direction`` pins only the forward ("fwd"), the backward ("bwd") or,
    on the two-level nest, the dk/dv kernel ("dkv"); default pins all."""

    def __init__(self, bq: int, bk: int, direction: str = "both"):
        self._blocks = (bq, bk)
        self._direction = direction

    def __enter__(self):
        self._prev = _FORCE.get(self._direction)
        _FORCE[self._direction] = self._blocks
        return self

    def __exit__(self, *exc):
        if self._prev is None:
            _FORCE.pop(self._direction, None)
        else:
            _FORCE[self._direction] = self._prev
        return False


def _fence(x):
    import numpy as np
    np.asarray(x)


def _bench_inputs(sq, sk, d, dtype, biased, batch, heads):
    import jax.numpy as jnp
    import numpy as np

    jdt = jnp.bfloat16 if str(dtype) == "bfloat16" else jnp.float32
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((batch, sq, heads, d)), jdt)
    k = jnp.asarray(rng.standard_normal((batch, sk, heads, d)), jdt)
    v = jnp.asarray(rng.standard_normal((batch, sk, heads, d)), jdt)
    bias = None
    if biased:
        bias = jnp.asarray(
            rng.standard_normal((batch, 1, 1, sk)) * 0.0, jnp.float32)
    return q, k, v, bias


def _first(out):
    return out[0] if isinstance(out, tuple) else out


def _wall_seconds(f, args, iters):
    """Wall seconds a call of ``f``, the fetch of its value fenced."""
    import time

    _fence(_first(f(*args)))                     # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*args)
    _fence(_first(out))
    return (time.perf_counter() - t0) / iters


def _sweep(sq, sk, make_fn, args, iters, verbose=False, oracle=None,
           rejected=None, clock=_wall_seconds):
    """``clock(make_fn(), args, iters)`` per viable (bq, bk) candidate
    with that candidate forced on every kernel; returns {(bq, bk): what
    the clock read}.

    ``oracle(bq, bk) -> list-of-failures`` (the armed differential
    oracle, ops/pallas/verify.py) runs BEFORE a candidate is timed: a
    failing candidate is never measured — a fast wrong kernel must not
    win — and its failures land in the caller's ``rejected`` dict.
    """
    results = {}
    for bq, bk in CANDIDATES:
        if bq > sq or bk > sk or sq % bq or sk % bk:
            continue
        if oracle is not None:
            bad = oracle(bq, bk)
            if bad:
                if rejected is not None:
                    rejected[(bq, bk)] = bad
                if verbose:
                    print(f"  ({bq},{bk}): REJECTED by oracle — {bad[0]}")
                continue
        try:
            with force_blocks(bq, bk):
                read = clock(make_fn(), args, iters)
            results[(bq, bk)] = read
            if verbose:
                ms = {k: round(v * 1e3, 3) for k, v in read.items()} \
                    if isinstance(read, dict) else f"{read * 1e3:.2f}"
                print(f"  ({bq},{bk}): {ms} ms")
        except Exception as e:                   # noqa: BLE001
            if verbose:
                print(f"  ({bq},{bk}): failed {e!r}")
    return results


def _candidate_oracle(d, dtype, causal, biased):
    """The armed differential oracle as a per-candidate gate, or None
    when FLAGS_pallas_verify is off (zero overhead: the sweep never
    calls into verify)."""
    from paddle_tpu.ops.pallas import verify
    if not verify.armed():
        return None

    def check(bq, bk):
        return verify.check_flash_candidate(
            bq, bk, d=d, dtype=str(dtype), causal=causal, biased=biased)

    return check


def _loss_fn(causal, bias):
    """A new function at every call: jax caches a trace by the function it
    was given, and the forced tile is not an argument, so one ``loss``
    jitted under two candidates would time the first candidate twice."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention as fa

    def loss(q_, k_, v_):
        out = fa.flash_attention(q_, k_, v_, causal=causal, bias=bias)
        return out.astype(jnp.float32).sum()

    return loss


def measure(sq, sk, d, dtype="bfloat16", causal=False, biased=False,
            batch=1, heads=8, iters=3, persist=True, verbose=False,
            rejected=None):
    """Time fwd+bwd per candidate on the current device; record winner.
    With FLAGS_pallas_verify armed, candidates failing the differential
    oracle are rejected (collected in ``rejected``) instead of timed,
    and the recorded winner is stamped ``verified: true``."""
    import jax

    q, k, v, bias = _bench_inputs(sq, sk, d, dtype, biased, batch, heads)
    oracle = _candidate_oracle(d, dtype, causal, biased)
    results = _sweep(sq, sk,
                     lambda: jax.jit(jax.value_and_grad(
                         _loss_fn(causal, bias), argnums=(0, 1, 2))),
                     (q, k, v), iters, verbose=verbose, oracle=oracle,
                     rejected=rejected)
    if not results:
        return None
    best = min(results, key=results.get)
    record(sq, sk, d, dtype, causal, biased, best, persist=persist,
           verified=oracle is not None)
    return best, results


_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def _kernel_seconds(f, args, iters):
    """{kernel name: device seconds a call} for the three flash kernels,
    read from a profiler trace of ``iters`` calls of ``f`` on the first
    chip: what the kernel itself takes, with no dispatch or fetch in it.
    (A wall-clock sweep of the same candidates ranked them differently:
    at 1-2 ms a call the host's share decides.  PERF.md, PR 26.)  Off the
    TPU (interpret mode: the tests) there is no device in a trace, and the
    call's wall time stands in for every kernel."""
    import glob
    import tempfile

    import jax

    from paddle_tpu.ops.pallas.common import backend_is_tpu

    if not backend_is_tpu():
        return dict.fromkeys(_KERNELS, _wall_seconds(f, args, iters))
    _fence(_first(f(*args)))                     # compile + warm
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(iters):
            out = f(*args)
        _fence(_first(out))
        jax.profiler.stop_trace()
        trace, = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                        "*.xplane.pb"))
        planes = jax.profiler.ProfileData.from_file(trace).planes
    ns = dict.fromkeys(_KERNELS, 0.0)
    for plane in planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for event in line.events:
                for kernel in _KERNELS:          # no name contains another
                    if kernel in event.name:
                        ns[kernel] += event.duration_ns
    return {kernel: t / iters / 1e9 for kernel, t in ns.items()}


def measure_split(sq, sk, d, dtype="bfloat16", causal=False, biased=False,
                  batch=1, heads=8, iters=3, persist=True, verbose=False,
                  rejected=None):
    """Tune the forward, the backward and, on the two-level nest, the dk/dv
    tile independently, in one sweep: every candidate is forced on all
    three kernels, forward + backward run ``iters`` times under a trace,
    and each kernel's own device time picks its winner (``_kernel_seconds``).
    The kernels are separate calls, so a kernel's time at a tile does not
    depend on the tiles of the other two.  On the three-axis grid dq and
    dk/dv share the "bwd" entry, which their summed time picks.

    Returns ((fwd_best, fwd_res), (bwd_best, bwd_res), (dkv_best,
    dkv_res) or None), each ``res`` {(bq, bk): seconds}; None where no
    candidate is viable.
    """
    import jax

    from paddle_tpu.ops.pallas import flash_attention as fa

    q, k, v, bias = _bench_inputs(sq, sk, d, dtype, biased, batch, heads)
    oracle = _candidate_oracle(d, dtype, causal, biased)
    times = _sweep(sq, sk,
                   lambda: jax.jit(jax.value_and_grad(
                       _loss_fn(causal, bias), argnums=(0, 1, 2))),
                   (q, k, v), iters, verbose=verbose, oracle=oracle,
                   rejected=rejected, clock=_kernel_seconds)
    if not times:
        return None
    # every candidate divides the sequences, so all run the same nest
    nest = fa._two_level(sq, sk, d, q.dtype, *next(iter(times)), biased)
    cost = {"fwd": lambda t: t["flash_fwd"],
            "bwd": lambda t: t["flash_bwd_dq"] + (
                0.0 if nest else t["flash_bwd_dkv"])}
    if nest:
        cost["dkv"] = lambda t: t["flash_bwd_dkv"]
    out = []
    for direction, of in cost.items():
        res = {blocks: of(t) for blocks, t in times.items()}
        best = min(res, key=res.get)
        record(sq, sk, d, dtype, causal, biased, best, persist=persist,
               direction=direction, verified=oracle is not None)
        out.append((best, res))
    return (*out, None) if not nest else tuple(out)
