"""Launcher tier (fleet/launch.py + launch_utils.py roles): env protocol,
log management, child supervision, PS launch mode."""
import os
import subprocess
import sys

LAUNCH = [sys.executable, "-m", "paddle_tpu.distributed.launch"]
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd, drop_env=()):
    env = {k: v for k, v in os.environ.items() if k not in drop_env}
    return subprocess.run(LAUNCH + args, cwd=cwd, capture_output=True,
                          text=True, timeout=120,
                          env=dict(env, PYTHONPATH=_REPO))


def test_collective_env_and_logs(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(
        "import os\n"
        "print('ID', os.environ['PADDLE_TRAINER_ID'])\n"
        "print('NUM', os.environ['PADDLE_TRAINERS_NUM'])\n"
        "print('EP', os.environ['PADDLE_TRAINER_ENDPOINTS'])\n")
    r = _run(["--log_dir", str(tmp_path / "log"), str(script)],
             cwd=str(tmp_path))
    assert r.returncode == 0, r.stderr
    log = (tmp_path / "log" / "workerlog.0").read_text()
    assert "ID 0" in log and "NUM 1" in log and "127.0.0.1:6070" in log


def test_child_failure_propagates(tmp_path):
    script = tmp_path / "boom.py"
    script.write_text("import sys; print('dying'); sys.exit(3)\n")
    r = _run(["--log_dir", str(tmp_path / "log"), str(script)],
             cwd=str(tmp_path))
    assert r.returncode == 3
    assert "exited with 3" in r.stderr
    assert "dying" in (tmp_path / "log" / "workerlog.0").read_text()


def test_ps_mode_roles_and_supervision(tmp_path):
    script = tmp_path / "ps.py"
    script.write_text(
        "import os\n"
        "role = os.environ['TRAINING_ROLE']\n"
        "print('ROLE', role,\n"
        "      os.environ.get('PADDLE_PSERVER_ID',\n"
        "                     os.environ.get('PADDLE_TRAINER_ID')))\n"
        "print('SERVERS', os.environ['PADDLE_PSERVERS_IP_PORT_LIST'])\n")
    r = _run(["--server_num", "2", "--worker_num", "2",
              "--log_dir", str(tmp_path / "log"), str(script)],
             cwd=str(tmp_path))
    assert r.returncode == 0, r.stderr
    s0 = (tmp_path / "log" / "serverlog.0").read_text()
    s1 = (tmp_path / "log" / "serverlog.1").read_text()
    w0 = (tmp_path / "log" / "workerlog.0").read_text()
    w1 = (tmp_path / "log" / "workerlog.1").read_text()
    assert "ROLE PSERVER 0" in s0 and "ROLE PSERVER 1" in s1
    assert "ROLE TRAINER 0" in w0 and "ROLE TRAINER 1" in w1
    # both tiers see the same 2-shard server list
    assert s0.count("127.0.0.1:6070") == 1 and "6071" in s0
    assert "6070" in w0 and "6071" in w1


def test_ps_failure_kills_job(tmp_path):
    script = tmp_path / "mixed.py"
    script.write_text(
        "import os, sys, time\n"
        "if os.environ['TRAINING_ROLE'] == 'PSERVER':\n"
        "    time.sleep(60)\n"       # would hang forever
        "sys.exit(5)\n")             # trainer dies immediately
    r = _run(["--server_num", "1", "--worker_num", "1",
              "--log_dir", str(tmp_path / "log"), str(script)],
             cwd=str(tmp_path))
    assert r.returncode == 5         # supervisor killed the server too


def test_ps_servers_are_pinned_to_the_host_platform(tmp_path):
    # one process per chip: a server that imports jax must not open the
    # accelerator its trainer needs
    script = tmp_path / "plat.py"
    script.write_text(
        "import os\n"
        "print(os.environ['TRAINING_ROLE'],\n"
        "      'JAX_PLATFORMS=' + os.environ.get('JAX_PLATFORMS', '<unset>'))\n")
    r = _run(["--server_num", "1", "--worker_num", "1",
              "--log_dir", str(tmp_path / "log"), str(script)],
             cwd=str(tmp_path), drop_env=("JAX_PLATFORMS",))
    assert r.returncode == 0, r.stderr
    assert "PSERVER JAX_PLATFORMS=cpu" in \
        (tmp_path / "log" / "serverlog.0").read_text()
    assert "TRAINER JAX_PLATFORMS=<unset>" in \
        (tmp_path / "log" / "workerlog.0").read_text()


def test_several_trainers_on_one_host_are_refused_off_cpu(tmp_path):
    script = tmp_path / "noop.py"
    script.write_text("print('ran')\n")
    r = _run(["--server_num", "1", "--worker_num", "2",
              "--log_dir", str(tmp_path / "log"), str(script)],
             cwd=str(tmp_path), drop_env=("JAX_PLATFORMS",))
    assert r.returncode == 2
    assert "racing for the same chip" in r.stderr
    assert not (tmp_path / "log").exists()     # nothing was started
