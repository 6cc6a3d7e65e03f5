"""Contracts that keep a run honest about the chip: one process per chip
(host-only children pin the cpu platform before the package import, and
importing the package starts no backend), no entry point that succeeds
without the TPU it was asked for, a compile cache placed from outside,
and a kernel oracle that does not count "did not run" as "verified"."""
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _src(*rel):
    with open(os.path.join(REPO, *rel)) as f:
        return f.read()


# -- one process per chip ----------------------------------------------------

def test_server_boot_pins_cpu_before_package_import():
    from paddle_tpu.distributed.ps.service import SERVER_BOOT
    upd = SERVER_BOOT.index("jax.config.update('jax_platforms', 'cpu')")
    imp = SERVER_BOOT.index("from paddle_tpu")
    assert upd < imp


def test_ps_spawners_use_server_boot():
    src = _src("tests", "test_ps_service.py")
    assert "SERVER_BOOT" in src
    # no one spawns the raw -m module (which imports the package first)
    assert "-m\", \"paddle_tpu.distributed.ps" not in src


def test_dataloader_workers_pin_cpu_before_package_import():
    init = _src("paddle_tpu", "__init__.py")
    assert init.index("PADDLE_TPU_WORKER") < init.index(
        "from paddle_tpu.core import")
    assert 'os.environ["PADDLE_TPU_WORKER"] = "1"' in _src(
        "paddle_tpu", "io", "__init__.py")


def test_print_signatures_pins_cpu():
    src = _src("tools", "print_signatures.py")
    assert "jax.config.update(\"jax_platforms\", \"cpu\")" in src
    assert src.index("jax_platforms") < src.index("MODULES")


def test_package_import_starts_no_backend():
    # a launcher (python -m paddle_tpu.distributed.launch) or a host-only
    # child imports the package; if that opened the chip, the trainer
    # started next could not have it
    code = ("import paddle_tpu, paddle_tpu.distributed.launch; "
            "import jax._src.xla_bridge as xb; "
            "assert not xb._backends, list(xb._backends)")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


# -- no run that succeeds without the chip it asked for ----------------------

def test_chip_smoke_exits_nonzero_without_tpu():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       cwd=REPO, capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout          # prints no result


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    # the driver refuses a last line with any other key (PR 21 was refused
    # for carrying the phase summary there)
    sys.path.insert(0, REPO)
    import chip_smoke
    out = json.loads(chip_smoke.result_line(
        True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}))
    assert out == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert list(out) == ["ok", "device"]
    assert list(out["device"]) == ["platform", "kind", "count"]


def test_set_device_tpu_raises_on_cpu():
    import paddle_tpu as paddle
    before = paddle.get_device()
    with pytest.raises(RuntimeError, match="no TPU"):
        paddle.set_device("tpu")
    with pytest.raises(RuntimeError, match="no TPU"):
        paddle.set_device("gpu:1")         # alias of tpu:1
    assert paddle.get_device() == before == "cpu"


def test_dryrun_multichip_raises_with_recipe_when_devices_short():
    import jax
    sys.path.insert(0, REPO)
    import __graft_entry__ as graft_entry
    n = 2 * len(jax.devices())
    with pytest.raises(RuntimeError) as e:
        graft_entry.dryrun_multichip(n)
    assert f"--xla_force_host_platform_device_count={n}" in str(e.value)
    assert "JAX_PLATFORMS=cpu" in str(e.value)


# -- a compile cache placed from outside -------------------------------------

def test_compile_cache_dir_resolution(monkeypatch, tmp_path):
    import jax

    import paddle_tpu as paddle
    options = ("jax_compilation_cache_dir",
               "jax_compilation_cache_include_metadata_in_key",
               "jax_hlo_source_file_canonicalization_regex")
    before, *others = [getattr(jax.config, name) for name in options]
    try:
        # set from outside: that directory, and no code names another
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert paddle.device.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        # an executable read back must carry this checkout's scope names
        # (PR 25), whatever directory the checkout lies in
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        assert re.sub(jax.config.jax_hlo_source_file_canonicalization_regex,
                      "", os.path.join(REPO, "paddle_tpu", "jit",
                                       "__init__.py")) \
            == os.path.join("paddle_tpu", "jit", "__init__.py")
        # unset: a fixed path inside the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache")
        assert paddle.device.use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        for name, value in zip(options, [before, *others]):
            jax.config.update(name, value)


def test_an_executable_read_back_carries_this_codes_scope_names(
        monkeypatch, tmp_path):
    """jax strips metadata from the cache key unless told otherwise, and
    a hit then returns the names of whoever compiled first: a profile
    would show stale ``jax.named_scope`` regions."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    import paddle_tpu as paddle
    options = ("jax_compilation_cache_dir",
               "jax_compilation_cache_include_metadata_in_key",
               "jax_hlo_source_file_canonicalization_regex",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes")
    before = [getattr(jax.config, name) for name in options]

    def scoped(name):
        def f(x):
            with jax.named_scope(name):
                return jnp.tanh(x @ x)
        return jax.jit(f).lower(jnp.ones((16, 16))).compile().as_text()

    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        assert "/before/" in scoped("before")
        assert "/before/" in scoped("stale")         # jax's default
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        paddle.device.use_compile_cache()
        entries = []
        for _ in range(2):         # one call site: its line is in the key
            assert "/after/" in scoped("after")
            entries.append(sorted(n for n in os.listdir(tmp_path)
                                  if n.endswith("-cache")))
        assert entries[0] == entries[1]              # and still a hit
    finally:
        for name, value in zip(options, before):
            jax.config.update(name, value)
        compilation_cache.reset_cache()


# -- "did not run" is not "verified" -----------------------------------------

def test_check_flash_candidate_fails_when_compiled_leg_raises(monkeypatch):
    from paddle_tpu.framework.flags import get_flags, set_flags
    from paddle_tpu.ops.pallas import common, verify
    with pytest.raises(RuntimeError, match="pallas_verify"):
        verify.check_flash_candidate(128, 128)     # disarmed checks nothing
    old = get_flags("pallas_verify")
    set_flags({"pallas_verify": True})
    # claim a TPU on the CPU host: the compiled leg then asks Mosaic for
    # a kernel this backend cannot build and raises — the oracle swallows
    # the fault, and the candidate must come back failed, not passed
    monkeypatch.setattr(common, "backend_is_tpu", lambda: True)
    try:
        failures = verify.check_flash_candidate(128, 128, grads=False)
    finally:
        set_flags(old)
    assert failures
    assert all(f["operand"].endswith(".oracle_fault") for f in failures)
