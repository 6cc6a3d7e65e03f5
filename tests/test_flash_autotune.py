"""Flash block autotune cache: lookup/record/force, kernel integration."""
import json

import pytest

from paddle_tpu.ops.pallas import autotune
from paddle_tpu.ops.pallas import flash_attention as fa


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setattr(autotune, "_PATH", str(tmp_path / "blocks.json"))
    monkeypatch.setattr(autotune, "_cache", None)
    yield
    autotune._cache = None


def test_lookup_miss_then_record():
    assert autotune.lookup(8192, 8192, 128, "bfloat16", True, False) is None
    autotune.record(8192, 8192, 128, "bfloat16", True, False, (256, 512))
    assert autotune.lookup(8192, 8192, 128, "bfloat16", True, False) == \
        (256, 512)
    # persisted
    with open(autotune._PATH) as f:
        data = json.load(f)
    assert data["8192x8192:d128:bfloat16:causal:nobias"] == [256, 512]


def test_reload_from_disk():
    autotune.record(1024, 1024, 64, "float32", False, True, (512, 256))
    autotune._cache = None                       # force reload
    assert autotune.lookup(1024, 1024, 64, "float32", False, True) == \
        (512, 256)


def test_force_blocks_overrides():
    autotune.record(2048, 2048, 128, "bfloat16", True, False, (512, 512))
    with autotune.force_blocks(256, 256):
        assert autotune.lookup(2048, 2048, 128, "bfloat16", True,
                               False) == (256, 256)
    assert autotune.lookup(2048, 2048, 128, "bfloat16", True, False) == \
        (512, 512)


def test_blocks_for_uses_cache_and_divisibility():
    autotune.record(4096, 4096, 128, "bfloat16", True, False, (1024, 512))
    assert fa._blocks_for(4096, 4096, 128, "bfloat16", True, False) == \
        (1024, 512)
    # miss -> heuristic, halved to divide the sequence
    bq, bk = fa._blocks_for(384, 384, 64, "float32", False, False)
    assert 384 % bq == 0 and 384 % bk == 0
    # cached preference halved when it does not divide this sequence
    autotune.record(768, 768, 64, "float32", False, False, (512, 512))
    bq, bk = fa._blocks_for(768, 768, 64, "float32", False, False)
    assert 768 % bq == 0 and 768 % bk == 0


def test_dkv_direction_falls_back_to_bwd_then_fwd():
    key = (1024, 1024, 64, "bfloat16", True, False)
    autotune.record(*key, (512, 512))
    assert autotune.lookup(*key, direction="dkv") == (512, 512)
    autotune.record(*key, (1024, 256), direction="bwd")
    assert autotune.lookup(*key, direction="dkv") == (1024, 256)
    autotune.record(*key, (128, 1024), direction="dkv")
    assert autotune.lookup(*key, direction="dkv") == (128, 1024)
    assert autotune.lookup(*key, direction="bwd") == (1024, 256)
    assert autotune.lookup(*key) == (512, 512)
    with open(autotune._PATH) as f:
        assert json.load(f)[
            "1024x1024:d64:bfloat16:causal:nobias:dkv"] == [128, 1024]
    # pinning the backward pins dk/dv too, unless dk/dv is pinned itself
    with autotune.force_blocks(256, 256, direction="bwd"):
        assert autotune.lookup(*key, direction="dkv") == (256, 256)
        assert autotune.lookup(*key) == (512, 512)
        with autotune.force_blocks(256, 512, direction="dkv"):
            assert autotune.lookup(*key, direction="dkv") == (256, 512)
            assert autotune.lookup(*key, direction="bwd") == (256, 256)


def test_fused_direction_falls_back_to_dkv_then_bwd_then_fwd():
    key = (1024, 1024, 64, "bfloat16", True, False)
    autotune.record(*key, (512, 512))
    assert autotune.lookup(*key, direction="fused") == (512, 512)
    autotune.record(*key, (1024, 256), direction="bwd")
    assert autotune.lookup(*key, direction="fused") == (1024, 256)
    autotune.record(*key, (128, 1024), direction="dkv")
    assert autotune.lookup(*key, direction="fused") == (128, 1024)
    autotune.record(*key, (256, 1024), direction="fused")
    assert autotune.lookup(*key, direction="fused") == (256, 1024)
    assert autotune.lookup(*key, direction="dkv") == (128, 1024)
    with open(autotune._PATH) as f:
        assert json.load(f)[
            "1024x1024:d64:bfloat16:causal:nobias:fused"] == [256, 1024]
    # pinning dk/dv pins the fused backward too, unless it is pinned itself
    with autotune.force_blocks(256, 256, direction="dkv"):
        assert autotune.lookup(*key, direction="fused") == (256, 256)
        with autotune.force_blocks(512, 1024, direction="fused"):
            assert autotune.lookup(*key, direction="fused") == (512, 1024)
            assert autotune.lookup(*key, direction="dkv") == (256, 256)


def test_distinct_mask_class_keys():
    autotune.record(2048, 2048, 64, "bfloat16", False, True, (256, 512))
    assert autotune.lookup(2048, 2048, 64, "bfloat16", False,
                           False) is None


def test_verified_record_is_stamped_dict():
    autotune.record(512, 512, 64, "bfloat16", True, False, (256, 256),
                    verified=True)
    with open(autotune._PATH) as f:
        data = json.load(f)
    assert data["512x512:d64:bfloat16:causal:nobias"] == \
        {"blocks": [256, 256], "verified": True}
    # lookup unwraps the stamped form, also across a disk reload
    assert autotune.lookup(512, 512, 64, "bfloat16", True, False) == \
        (256, 256)
    autotune._cache = None
    assert autotune.lookup(512, 512, 64, "bfloat16", True, False) == \
        (256, 256)


def test_sweep_rejects_oracle_failures(monkeypatch):
    """A candidate failing the differential oracle is never timed and
    lands in the caller's rejected dict; passing candidates still run."""
    monkeypatch.setattr(autotune, "CANDIDATES", [(256, 256), (256, 512)])
    timed = []

    def make_fn():
        def f():
            timed.append(autotune._FORCE.get("both"))
            return 0.0
        return f

    def oracle(bq, bk):
        if (bq, bk) == (256, 256):
            return [{"sq": 384, "sk": 384, "dtype": "bfloat16",
                     "operand": "flash[256x256].dq"}]
        return []

    rejected = {}
    results = autotune._sweep(512, 512, make_fn, (), iters=1,
                              oracle=oracle, rejected=rejected)
    assert (256, 256) not in results and (256, 512) in results
    assert list(rejected) == [(256, 256)]
    assert rejected[(256, 256)][0]["operand"] == "flash[256x256].dq"
    assert all(t == (256, 512) for t in timed)


def test_candidate_oracle_disarmed_is_none():
    from paddle_tpu.framework.flags import flag, set_flags
    assert not flag("pallas_verify")
    assert autotune._candidate_oracle(64, "bfloat16", True, False) is None
    set_flags({"pallas_verify": True})
    try:
        assert autotune._candidate_oracle(
            64, "bfloat16", True, False) is not None
    finally:
        set_flags({"pallas_verify": False})


def test_split_sweep_traces_every_candidate(monkeypatch):
    """jax caches a trace by the function it was given and the forced tile
    is no argument: one ``loss`` jitted under each candidate would time the
    first candidate's kernels every time.  The counters say what was built."""
    from paddle_tpu.framework import monitor
    monkeypatch.setattr(autotune, "CANDIDATES", [(128, 128), (256, 256)])
    monkeypatch.setattr(fa, "_INTERPRET", True)
    traced = []
    real = autotune.force_blocks.__exit__

    def exit_and_read(self, *exc):
        traced.append(monitor.get_stat("flash_subtiles_computed_total"))
        return real(self, *exc)

    monkeypatch.setattr(autotune.force_blocks, "__exit__", exit_and_read)
    before = monitor.get_stat("flash_subtiles_computed_total")
    out = autotune.measure_split(
        256, 256, 64, "float32", causal=True, batch=1, heads=1, iters=1,
        persist=False)
    # one sweep: two kernels a candidate, 3 of 4 sub-tiles each at
    # (128, 128), then 1 of 1
    assert [traced[0] - before, traced[1] - traced[0]] == [6, 2]
    # this call runs the two-level nest and its fused backward, which has
    # an entry of its own
    assert list(out) == ["fwd", "fused"]
    assert set(out["fwd"][1]) == set(out["fused"][1]) == {(128, 128),
                                                          (256, 256)}
    for direction, (best, _) in out.items():
        assert autotune.lookup(256, 256, 64, "float32", True, False,
                               direction=direction) == best


def test_split_sweep_picks_each_kernel_by_its_own_time(monkeypatch):
    """Each direction's winner is the tile at which its own kernel is
    fastest; on the three-axis grid dq and dk/dv share the "bwd" entry."""
    # seconds of (flash_fwd, flash_bwd_dq, flash_bwd_dkv, flash_bwd_dq_dkv)
    clock = {(128, 128): (3.0, 1.0, 9.0, 4.0),
             (256, 256): (1.0, 5.0, 2.0, 3.0),
             (128, 256): (2.0, 2.0, 1.0, 5.0)}
    names = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
             "flash_bwd_dq_dkv")
    monkeypatch.setattr(autotune, "CANDIDATES", list(clock))
    monkeypatch.setattr(
        autotune, "_kernel_seconds", lambda f, args, iters: dict(zip(
            names, clock[autotune._FORCE["both"]])))
    best = lambda out: {dr: b for dr, (b, _) in out.items()}
    # the nest, its fused backward
    assert best(autotune.measure_split(
        256, 256, 64, "float32", causal=True, persist=False)) == {
            "fwd": (256, 256), "fused": (256, 256)}
    # the nest where the dQ accumulator does not fit: dq and dk/dv apart
    monkeypatch.setattr(fa, "_fused_bwd_fits", lambda *_: False)
    assert best(autotune.measure_split(
        256, 256, 64, "float32", causal=True, persist=False)) == {
            "fwd": (256, 256), "bwd": (128, 128), "dkv": (128, 256)}
    # a biased call keeps the old grid: one entry for both backward kernels
    assert best(autotune.measure_split(
        256, 256, 64, "float32", causal=True, biased=True,
        persist=False)) == {"fwd": (256, 256), "bwd": (128, 256)}
