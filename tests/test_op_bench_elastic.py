"""tools/op_bench.py harness (op_tester.cc + check_op_benchmark_result.py
roles) and launcher --elastic_retries (failure-recovery tier)."""
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))


class TestOpBench:
    @pytest.mark.slow      # heavy for the 870 s tier-1 cap (PR 21): -m slow
    def test_run_one_and_gate(self, tmp_path):
        import op_bench
        cfg = [{"name": "small_matmul", "op": "paddle_tpu.matmul",
                "args": [{"shape": [32, 32], "dtype": "float32"},
                         {"shape": [32, 32], "dtype": "float32"}]}]
        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text(json.dumps(cfg))
        base_path = str(tmp_path / "base.json")
        rc = op_bench.main(["--config", str(cfg_path), "--save", base_path,
                            "--iters", "2"])
        assert rc == 0
        base = json.load(open(base_path))
        assert base[0]["name"] == "small_matmul" and base[0]["ms"] > 0

        # same speed → gate passes
        rc = op_bench.main(["--config", str(cfg_path), "--compare",
                            base_path, "--threshold", "5.0", "--iters", "2"])
        assert rc == 0

        # artificially fast baseline → regression detected
        base[0]["ms"] = 1e-9
        fast = str(tmp_path / "fast.json")
        json.dump(base, open(fast, "w"))
        rc = op_bench.main(["--config", str(cfg_path), "--compare", fast,
                            "--threshold", "0.1", "--iters", "2"])
        assert rc == 1

    def test_error_config_reported_not_fatal(self, tmp_path, capsys):
        import op_bench
        cfg = [{"name": "broken", "op": "paddle_tpu.does_not_exist",
                "args": []}]
        p = tmp_path / "c.json"
        p.write_text(json.dumps(cfg))
        rc = op_bench.main(["--config", str(p)])
        assert rc == 0
        assert "error" in capsys.readouterr().out


class TestElasticRestart:
    def test_child_restarted_then_succeeds(self, tmp_path):
        """Child fails on first run, succeeds on second — job exits 0
        with --elastic_retries 2."""
        marker = tmp_path / "ran_once"
        script = tmp_path / "flaky.py"
        script.write_text(
            "import os, sys\n"
            f"m = {str(repr(str(marker)))}\n"
            "if not os.path.exists(m):\n"
            "    open(m, 'w').close()\n"
            "    sys.exit(7)\n"
            "print('recovered')\n")
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--elastic_retries", "2",
             "--log_dir", str(tmp_path / "log"), str(script)],
            cwd=str(tmp_path), capture_output=True, text=True, timeout=120,
            env=dict(os.environ, PYTHONPATH=_REPO))
        assert r.returncode == 0, r.stderr
        assert "elastic restart 1/2" in r.stderr
        log = (tmp_path / "log" / "workerlog.0").read_text()
        assert "recovered" in log

    def test_retries_exhausted_fails(self, tmp_path):
        script = tmp_path / "dead.py"
        script.write_text("import sys; sys.exit(9)\n")
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--elastic_retries", "1",
             "--log_dir", str(tmp_path / "log"), str(script)],
            cwd=str(tmp_path), capture_output=True, text=True, timeout=120,
            env=dict(os.environ, PYTHONPATH=_REPO))
        assert r.returncode == 9
        assert "elastic restart 1/1" in r.stderr
