"""Elastic training: membership epochs, hang/straggler watchdog, and
shrink-to-survive re-sharding.

The acceptance run (TestShrinkToSurvive) is the deterministic chaos
suite the ISSUE demands: ``elastic.lease`` faults injected into a
4-worker in-process data-parallel job make one worker's renewal fail,
its lease expires under a fake clock, the membership epoch bumps, the
survivors re-form via ``reform()`` (role refresh + latest-slot restore)
and the shrunk 3-worker job reaches the same final loss as an
uninterrupted 3-worker run.  The hang watchdog (``elastic.worker_hang``
latency + ElasticAgent deadline) and a real SIGKILL-mid-epoch
multi-process re-form (FileStore, marked slow) complete the story.
"""
import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import optimizer
from paddle_tpu.distributed.elastic import (DictStore, ElasticAgent,
                                            ElasticWorkerContext, Evicted,
                                            FileStore, LeaseExpired,
                                            LocalHandle, dp_shard, reform,
                                            reshard_tables)
from paddle_tpu.distributed.fleet.role_maker import (PaddleCloudRoleMaker,
                                                     UserDefinedRoleMaker)
from paddle_tpu.framework import chaos
from paddle_tpu.framework.auto_checkpoint import (TrainEpochRange,
                                                  latest_checkpoint)
from paddle_tpu.jit import (TrainStep, apply_functional_update,
                            functional_loss_call)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_registry():
    chaos.reset(seed=0)
    yield
    chaos.reset(seed=0)


class _Clock:
    """Injectable deterministic clock for the store."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, d):
        self.t += d


# ---------------------------------------------------------------------------
# the rendezvous store: leases + epochs
# ---------------------------------------------------------------------------

class TestStore:
    def test_membership_epochs(self):
        clock = _Clock()
        s = DictStore(ttl=2.0, clock=clock)
        assert s.epoch() == 0
        for i in range(3):
            s.register(f"w{i}", endpoint=f"h{i}:1")
        assert s.epoch() == 3 and s.members() == ["w0", "w1", "w2"]
        s.renew("w0")
        s.beat("w1", step=7)
        assert s.epoch() == 3                    # renew/beat never bump
        assert s.leave("w2") == 4
        assert s.leave("w2") == 4                # idempotent
        epoch, members, endpoints = s.membership()
        assert (epoch, members, endpoints) == (4, ["w0", "w1"],
                                               ["h0:1", "h1:1"])

    def test_sweep_expires_and_bumps_once(self):
        clock = _Clock()
        s = DictStore(ttl=2.0, clock=clock)
        for i in range(3):
            s.register(f"w{i}")
        clock.advance(1.0)
        s.renew("w1")
        clock.advance(1.5)                       # w0/w2 past ttl, w1 not
        assert sorted(s.sweep()) == ["w0", "w2"]
        assert s.epoch() == 4 and s.members() == ["w1"]
        assert s.sweep() == [] and s.epoch() == 4

    def test_renew_after_sweep_raises(self):
        clock = _Clock()
        s = DictStore(ttl=1.0, clock=clock)
        s.register("w0")
        clock.advance(2.0)
        s.sweep()
        with pytest.raises(LeaseExpired):
            s.renew("w0")
        # re-register is the way back in (grow-on-join) and bumps again
        assert s.register("w0") == 3

    def test_lease_chaos_point_is_a_lost_renewal(self):
        clock = _Clock()
        s = DictStore(ttl=1.5, clock=clock)
        s.register("a")
        s.register("b")
        with chaos.inject("elastic.lease", mode="error", nth=2, n_times=1):
            s.renew("a")
            with pytest.raises(chaos.InjectedFault):
                s.renew("b")
        # b's lease now runs out exactly like a crash
        clock.advance(1.0)
        s.renew("a")
        clock.advance(0.8)
        assert s.sweep() == ["b"]
        assert s.members() == ["a"]

    def test_progress_tracks_beats_and_step(self):
        clock = _Clock()
        s = DictStore(ttl=10.0, clock=clock)
        s.register("w0")
        assert s.progress("w0") == (0.0, -1)     # never beaten: exempt
        s.beat("w0", step=3)
        clock.advance(4.0)
        age, step = s.progress("w0")
        assert age == 4.0 and step == 3
        assert s.progress("nope") is None

    def test_reregister_without_endpoint_keeps_recorded_one(self):
        s = DictStore(ttl=5.0)
        s.register("w0", endpoint="h0:1234")
        s.register("w0")                         # agent-style re-register
        assert s.membership()[2] == ["h0:1234"]
        s.register("w0", endpoint="h0:9999")     # explicit update wins
        assert s.membership()[2] == ["h0:9999"]

    def test_reregister_of_live_lease_does_not_bump(self):
        """Launcher registers, then the elastic-aware worker join()s:
        one membership change, not two — a second bump would make every
        survivor run a redundant full re-form."""
        clock = _Clock()
        s = DictStore(ttl=5.0, clock=clock)
        assert s.register("w0") == 1
        assert s.register("w0") == 1             # idempotent: no bump
        clock.advance(4.0)
        assert s.register("w0") == 1             # and the lease refreshed
        clock.advance(4.0)
        assert s.sweep() == []                   # renewed at t=4, ttl 5
        clock.advance(2.0)
        assert s.sweep() == ["w0"]               # expiry still works
        assert s.register("w0") == 3             # rejoin after sweep bumps

    def test_file_store_shared_across_instances(self, tmp_path):
        p = str(tmp_path / "rdv.json")
        a, b = FileStore(p, ttl=5.0), FileStore(p, ttl=5.0)
        a.register("w0", "h0:1")
        b.register("w1", "h1:1")
        assert a.membership() == b.membership() == \
            (2, ["w0", "w1"], ["h0:1", "h1:1"])
        b.leave("w0")
        assert a.members() == ["w1"] and a.epoch() == 3


# ---------------------------------------------------------------------------
# role maker: refresh mid-job (env + store), satellite worker_num fix
# ---------------------------------------------------------------------------

class TestRoleMakerRefresh:
    def test_env_refresh_rereads_snapshot(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TRAINER_ID", "1")
        monkeypatch.setenv("PADDLE_TRAINER_ENDPOINTS", "a:1,b:1")
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "2")
        rm = PaddleCloudRoleMaker(is_collective=True)
        assert rm.worker_index() == 1 and rm.worker_num() == 2
        # the relaunched job exports a fresh, smaller block
        monkeypatch.setenv("PADDLE_TRAINER_ID", "0")
        monkeypatch.setenv("PADDLE_TRAINER_ENDPOINTS", "a:1")
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "1")
        assert rm.worker_num() == 1              # env read is live
        rm.refresh()
        assert rm.worker_index() == 0
        assert rm.get_trainer_endpoints() == ["a:1"]

    def test_store_refresh_overrides_stale_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")   # launcher's lie
        s = DictStore(ttl=5.0)
        for i in range(3):
            s.register(f"w{i}", endpoint=f"h{i}:1")
        rm = PaddleCloudRoleMaker(is_collective=True)
        assert rm.worker_num() == 4
        rm.refresh(store=s, worker_id="w2")
        assert rm.worker_num() == 3              # live members win
        assert rm.worker_index() == 2
        assert rm.get_trainer_endpoints() == ["h0:1", "h1:1", "h2:1"]
        # shrink: w0 leaves; a second refresh re-ranks the survivors
        s.leave("w0")
        rm.refresh(store=s)                      # worker_id remembered
        assert rm.worker_num() == 2 and rm.worker_index() == 1

    def test_refresh_raises_evicted_for_non_member(self):
        s = DictStore(ttl=5.0)
        s.register("w0")
        rm = PaddleCloudRoleMaker(is_collective=True)
        with pytest.raises(Evicted):
            rm.refresh(store=s, worker_id="w9")

    def test_user_defined_worker_num_ignores_env(self, monkeypatch):
        """Satellite: PADDLE_TRAINERS_NUM must not silently override an
        explicitly passed endpoint list."""
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "7")
        rm = UserDefinedRoleMaker(worker_endpoints=["a:1", "b:1"])
        assert rm.worker_num() == 2
        rm.refresh()                             # no env to re-read: no-op
        assert rm.worker_num() == 2
        # no explicit list: nothing to win — the env fallback survives
        # (PS launches export only the count, not trainer endpoints)
        assert UserDefinedRoleMaker().worker_num() == 7


# ---------------------------------------------------------------------------
# heartbeat monitor: revival + flap accounting (satellite)
# ---------------------------------------------------------------------------

class TestHeartbeatFlaps:
    def test_marked_dead_worker_revives_and_flaps_counted(self):
        from paddle_tpu.distributed.ps.service import HeartBeatMonitor
        mon = HeartBeatMonitor(timeout=5.0)
        revived = []
        mon.on_revive = lambda w, n: revived.append((w, n))
        mon.beat("w0")
        mon.mark_dead("w0")
        assert "w0" in mon.dead_workers()
        mon.beat("w0")                           # the flap
        assert "w0" not in mon.dead_workers()
        assert mon.flap_count("w0") == 1
        assert revived == [("w0", 1)]
        mon.mark_dead("w0")
        mon.beat("w0")
        assert mon.flap_count("w0") == 2         # flaky, not gone
        assert mon.flap_count("w1") == 0

    def test_on_dead_fires_again_after_revival(self):
        from paddle_tpu.distributed.ps.service import HeartBeatMonitor
        mon = HeartBeatMonitor(timeout=5.0)
        deaths = []
        mon.on_dead = lambda w: deaths.append(w)
        mon.mark_dead("w0")
        mon.mark_dead("w0")                      # duplicate: one report
        mon.beat("w0")
        mon.mark_dead("w0")                      # fresh death re-reports
        assert deaths == ["w0", "w0"]


# ---------------------------------------------------------------------------
# launch supervisor satellites: restart backoff, budget reset, zombie reap
# ---------------------------------------------------------------------------

class TestSuperviseBackoff:
    def test_instant_crash_cannot_burn_budget_in_a_blink(self):
        from paddle_tpu.distributed.launch import _Child, _supervise
        c = _Child("t", [sys.executable, "-c", "import sys; sys.exit(1)"],
                   {}, None)
        t0 = time.monotonic()
        rc = _supervise([c], elastic_retries=2, restart_backoff=0.3,
                        healthy_interval=60.0, poll_interval=0.02)
        elapsed = time.monotonic() - t0
        assert rc == 1 and c.restarts == 2
        assert elapsed >= 0.3 + 0.6              # 0.3 * 2^0 + 0.3 * 2^1

    def test_budget_resets_after_healthy_interval(self, tmp_path):
        from paddle_tpu.distributed.launch import _Child, _supervise
        marker = tmp_path / "count"
        code = (
            "import os, sys, time\n"
            f"p = {str(marker)!r}\n"
            "n = int(open(p).read()) if os.path.exists(p) else 0\n"
            "n += 1\n"
            "open(p, 'w').write(str(n))\n"
            "if n == 1: sys.exit(1)\n"
            "if n == 2: time.sleep(0.8); sys.exit(1)\n"
            "sys.exit(0)\n")
        c = _Child("t", [sys.executable, "-c", code], {}, None)
        rc = _supervise([c], elastic_retries=1, restart_backoff=0.02,
                        healthy_interval=0.4, poll_interval=0.02)
        # without the reset the 2nd crash would exhaust retries (1) and
        # fail the job; with it, incarnation 3 runs and exits 0
        assert rc == 0
        assert marker.read_text() == "3"

    def test_terminate_reaps_sigkilled_child(self, tmp_path):
        from paddle_tpu.distributed.launch import _Child
        log = tmp_path / "child.log"
        c = _Child("t", [sys.executable, "-c",
                         "import signal, time\n"
                         "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
                         "print('R', flush=True)\n"
                         "time.sleep(60)\n"],
                   {}, str(log))
        # wait until the handler is installed (the R lands after it)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if log.exists() and "R" in log.read_text():
                break
            time.sleep(0.05)
        else:
            pytest.fail("child never came up")
        c.terminate(grace=0.5)
        # escalated to SIGKILL *and reaped*: poll() sees the real status
        # instead of a zombie's None
        assert c.proc.poll() == -signal.SIGKILL


# ---------------------------------------------------------------------------
# elastic agent: crash restart, shrink-to-survive, hang watchdog
# ---------------------------------------------------------------------------

def _drive(agent, pred, timeout=10.0, interval=0.03):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        agent.poll_once()
        if pred(agent.events):
            return True
        time.sleep(interval)
    return False


def _has(events, kind, name=None):
    return any(ev[0] == kind and (name is None or ev[1] == name)
               for ev in events)


class TestElasticAgent:
    def test_crash_is_restarted_with_backoff_then_job_completes(self):
        store = DictStore(ttl=60.0)
        runs = {"n": 0}

        def target(stop):
            runs["n"] += 1
            if runs["n"] == 1:
                raise RuntimeError("boom")
            for i in range(3):
                store.beat("w0", i)
                time.sleep(0.01)

        h = LocalHandle("w0", target)
        store.register("w0")
        h.start()
        agent = ElasticAgent(store, [h], hang_deadline=60.0,
                             elastic_retries=1, restart_backoff=0.05)
        assert _drive(agent, lambda ev: _has(ev, "done"))
        assert _has(agent.events, "crashed", "w0")
        assert _has(agent.events, "restart_scheduled", "w0")
        assert _has(agent.events, "restarted", "w0")
        assert runs["n"] == 2 and not agent.failed()

    def test_replacement_waits_until_killed_child_is_gone(self):
        """One process per chip: a killed child that has not been reaped
        yet still holds the chip, so the agent must not start its
        replacement beside it — it kills again and looks next pass."""
        from paddle_tpu.distributed.elastic import WorkerHandle

        class Lingering(WorkerHandle):
            name = "w0"
            kills = restarts = 0
            lingers = 2               # outlives this many kill() calls

            def alive(self):
                return self.kills <= self.lingers and self.restarts == 0

            def exit_code(self):
                return None if self.alive() else -9

            def kill(self, grace=0.0):
                self.kills += 1

            def restart(self):
                assert not self.alive(), "replacement raced the old child"
                self.restarts += 1

        store = DictStore(ttl=60.0)
        store.register("w0")
        store.beat("w0", 0)
        h = Lingering()
        now = [0.0]
        agent = ElasticAgent(store, [h], hang_deadline=0.0,
                             elastic_retries=1, restart_backoff=0.0,
                             clock=lambda: now[0])
        for _ in range(6):
            now[0] += 1.0
            agent.poll_once()
            if h.restarts:
                break
        assert _has(agent.events, "hang_killed", "w0")
        assert h.restarts == 1 and h.kills == h.lingers + 1

    def test_out_of_budget_worker_shrinks_not_kills(self):
        store = DictStore(ttl=60.0)

        def crasher(stop):
            raise RuntimeError("always")

        def healthy(stop):
            time.sleep(0.2)

        hc, hh = LocalHandle("bad", crasher), LocalHandle("ok", healthy)
        for h in (hc, hh):
            store.register(h.name)
            h.start()
        agent = ElasticAgent(store, [hc, hh], hang_deadline=60.0,
                             elastic_retries=0, min_world=1)
        assert _drive(agent, lambda ev: _has(ev, "done"))
        assert _has(agent.events, "shrunk", "bad")
        assert not agent.failed()
        # membership followed: "bad" left at crash, "ok" left cleanly
        # at exit (a deliberate leave, not a ttl expiry)
        assert _has(agent.events, "left", "ok")
        assert not _has(agent.events, "lease_expired")
        assert store.members() == []

    def test_last_worker_out_of_budget_fails_job(self):
        store = DictStore(ttl=60.0)

        def crasher(stop):
            raise RuntimeError("always")

        h = LocalHandle("w0", crasher)
        store.register("w0")
        h.start()
        agent = ElasticAgent(store, [h], elastic_retries=0, min_world=1)
        assert _drive(agent, lambda ev: _has(ev, "failed"), timeout=5.0)
        assert agent.failed()
        # terminal state: further passes neither re-emit nor report done
        agent.poll_once()
        agent.poll_once()
        assert [ev[0] for ev in agent.events].count("failed") == 1
        assert [ev[0] for ev in agent.events].count("crashed") == 1
        assert not _has(agent.events, "done")

    def test_hung_worker_killed_and_replaced_within_deadline(self):
        """Acceptance: a hung worker is detected and replaced within the
        configured deadline without operator input.  The hang is a real
        injected ``elastic.worker_hang`` latency — the straggler sleeps
        inside its liveness beat, its progress age crosses the deadline,
        and the agent kills + replaces it long before the sleep ends."""
        store = DictStore(ttl=60.0)
        hang_s = 3.0
        deadline_s = 0.3
        chaos.arm("elastic.worker_hang", mode="latency", latency=hang_s,
                  nth=40, n_times=1)
        handles = []

        def make(name):
            def target(stop):
                ctx = ElasticWorkerContext(store, name)
                ctx.join()
                step = 0
                while not stop.is_set():
                    try:
                        ctx.step_done(step)
                    except (LeaseExpired, chaos.InjectedFault):
                        return
                    step += 1
                    time.sleep(0.01)
            return target

        for name in ("wa", "wb"):
            h = LocalHandle(name, make(name))
            handles.append(h)
            h.start()
        agent = ElasticAgent(store, handles, hang_deadline=deadline_s,
                             elastic_retries=2, restart_backoff=0.05)
        t0 = time.monotonic()
        try:
            assert _drive(
                agent,
                lambda ev: (_has(ev, "hang_killed") and
                            _has(ev, "restarted")),
                timeout=8.0)
            detect = time.monotonic() - t0
            # detected + replaced while the straggler is still asleep
            assert detect < hang_s
            kill = next(ev for ev in agent.events
                        if ev[0] == "hang_killed")
            assert kill[2] > deadline_s          # the age that tripped it
            # the replacement re-registered: membership is whole again
            assert store.members() == ["wa", "wb"]
        finally:
            for h in handles:
                h.kill()

    def test_min_world_counts_members_only(self):
        """A supervised-but-non-member handle (a PS server) must not
        count as a survivor: losing the last trainer fails the job even
        while servers run on."""
        store = DictStore(ttl=60.0)

        def crasher(stop):
            raise RuntimeError("always")

        def server(stop):
            while not stop.is_set():
                time.sleep(0.02)

        tr, sv = LocalHandle("trainer-0", crasher), \
            LocalHandle("server-0", server)
        store.register("trainer-0")
        tr.start()
        sv.start()
        agent = ElasticAgent(store, [tr, sv], elastic_retries=0,
                             min_world=1, member_names=["trainer-0"])
        try:
            assert _drive(agent, lambda ev: _has(ev, "failed"),
                          timeout=5.0)
            assert not _has(agent.events, "shrunk")
        finally:
            sv.kill()

    def test_plain_script_without_beats_is_exempt_from_hang_kill(self):
        store = DictStore(ttl=60.0)

        def silent(stop):                        # never beats progress
            time.sleep(0.4)

        h = LocalHandle("w0", silent)
        store.register("w0")
        h.start()
        agent = ElasticAgent(store, [h], hang_deadline=0.05)
        assert _drive(agent, lambda ev: _has(ev, "done"), timeout=5.0)
        assert not _has(agent.events, "hang_killed")

    def test_first_beat_deadline_catches_init_hang(self):
        """Opt-in for elastic-aware trainers: a worker that registered
        but hangs BEFORE its first beat (deadlocked init) is killed at
        first_beat_deadline instead of being exempt forever."""
        store = DictStore(ttl=60.0)

        def init_hung(stop):                     # joins via the launcher
            while not stop.is_set():             # path, never beats
                time.sleep(0.02)

        h = LocalHandle("w0", init_hung)
        store.register("w0")
        h.start()
        agent = ElasticAgent(store, [h], hang_deadline=60.0,
                             elastic_retries=0,
                             first_beat_deadline=0.2)
        try:
            assert _drive(agent, lambda ev: _has(ev, "hang_killed"),
                          timeout=5.0)
        finally:
            h.kill()


# ---------------------------------------------------------------------------
# PS tier: epoch fencing + shrink re-shard
# ---------------------------------------------------------------------------

def _ps_servers(n, rows=12, dim=4, fill=None, table_optimizer="sgd"):
    from paddle_tpu.distributed.ps import HostEmbeddingTable
    from paddle_tpu.distributed.ps.service import PsServer
    servers = []
    for s in range(n):
        t = HostEmbeddingTable(rows, dim, optimizer=table_optimizer,
                               learning_rate=1.0)
        if fill is not None:
            t._table[:] = fill(s)
        srv = PsServer({"emb": t}, port=0)
        srv.start()
        servers.append(srv)
    return servers, [f"127.0.0.1:{s.port}" for s in servers]


class TestEpochFencing:
    def test_stale_epoch_push_rejected_current_accepted(self):
        from paddle_tpu.distributed.ps.service import PsClient
        servers, eps = _ps_servers(1)
        try:
            table = servers[0].tables["emb"]
            before = table._table.copy()
            stale = PsClient(eps, backoff_base=0.01)
            fresh = PsClient(eps, backoff_base=0.01)
            stale.set_epoch(1)
            fresh.set_epoch(2, fence_servers=True)
            assert servers[0].epoch == 2
            with pytest.raises(RuntimeError, match="stale membership"):
                stale.push("emb", np.array([1]),
                           np.ones((1, 4), np.float32))
            np.testing.assert_array_equal(table._table, before)
            fresh.push("emb", np.array([1]), np.ones((1, 4), np.float32))
            np.testing.assert_allclose(table._table[1], before[1] - 1.0)
            # reads stay open so the stale worker can see its error state
            stale.pull("emb", np.array([0]))
        finally:
            for s in servers:
                s.shutdown()

    def test_set_epoch_resizes_bye_quorum(self):
        """The re-form fence carries the new world size: a shrunk job's
        servers must shut down after byes from the SURVIVORS, not wait
        forever for workers that no longer exist."""
        from paddle_tpu.distributed.ps.service import PsClient
        servers, eps = _ps_servers(1)
        try:
            servers[0].n_workers = 4
            c = PsClient(eps, backoff_base=0.01)
            c.set_epoch(2, fence_servers=True, n_workers=3)
            assert servers[0].n_workers == 3 and servers[0].epoch == 2
            # without n_workers the quorum is left alone
            c.set_epoch(3, fence_servers=True)
            assert servers[0].n_workers == 3
            # a slower survivor's STALE re-form cannot roll it back
            stale = PsClient(eps, backoff_base=0.01)
            stale.set_epoch(2, fence_servers=True, n_workers=4)
            assert servers[0].n_workers == 3 and servers[0].epoch == 3
        finally:
            for s in servers:
                s.shutdown()

    def test_stale_bye_does_not_count_toward_shrunk_quorum(self):
        """An evicted worker's graceful exit must not tip a shrunk bye
        quorum and shut the servers down under the survivors."""
        from paddle_tpu.distributed.ps.service import PsClient
        servers, eps = _ps_servers(1)
        try:
            srv = servers[0]
            srv.n_workers = 2                    # already-shrunk quorum
            stale = PsClient(eps, backoff_base=0.01)
            stale.set_epoch(1)
            fresh = PsClient(eps, backoff_base=0.01)
            fresh.set_epoch(2, fence_servers=True)
            stale.bye()                          # evicted worker leaving
            assert srv._bye_count == 0           # not counted
            fresh.bye()
            assert srv._bye_count == 1           # survivors still count
        finally:
            for s in servers:
                s.shutdown()

    def test_reform_quorum_discards_previous_generation_byes(self):
        """A re-form that resizes the quorum also resets the bye count:
        byes banked under the old membership must not tip the shrunk
        quorum and shut servers down under a still-training survivor."""
        from paddle_tpu.distributed.ps.service import PsClient
        servers, eps = _ps_servers(1)
        try:
            srv = servers[0]
            srv.n_workers = 4
            early = PsClient(eps, backoff_base=0.01)
            early.bye()                          # pre-fence clean finish
            assert srv._bye_count == 1
            survivor = PsClient(eps, backoff_base=0.01)
            survivor.set_epoch(1, fence_servers=True, n_workers=3)
            assert srv._bye_count == 0           # old generation discarded
        finally:
            for s in servers:
                s.shutdown()

    def test_epochless_clients_ok_until_first_fence(self):
        """Back-compat: a non-elastic job (no fence ever installed)
        accepts unstamped pushes — but once the job has fenced, an
        unstamped mutation is as stale as an old-epoch one (the wake-up
        path of a worker that slept through the whole re-form)."""
        from paddle_tpu.distributed.ps.service import PsClient
        servers, eps = _ps_servers(1)
        try:
            c = PsClient(eps, backoff_base=0.01)
            c.push("emb", np.array([2]), np.ones((1, 4), np.float32))
            assert c.stat()["epoch"] == 0
            fencer = PsClient(eps, backoff_base=0.01)
            fencer.set_epoch(3, fence_servers=True)
            with pytest.raises(RuntimeError, match="stale membership"):
                c.push("emb", np.array([2]), np.ones((1, 4), np.float32))
        finally:
            for s in servers:
                s.shutdown()


class TestReshard:
    def test_shrink_reshard_moves_rows_to_new_owners(self):
        olds, old_eps = _ps_servers(3, fill=lambda s: float(s + 1))
        news, new_eps = _ps_servers(2, fill=lambda s: 0.0)
        try:
            report = reshard_tables(old_eps, new_eps, ["emb"], epoch=5)
            assert report == {"emb": 0}
            expect = np.array([(r % 3) + 1 for r in range(12)], np.float32)
            for srv in news:
                np.testing.assert_allclose(
                    srv.tables["emb"]._table[:, 0], expect)
                assert srv.epoch == 5            # fence installed
        finally:
            for s in olds + news:
                s.shutdown()

    def test_dead_owner_rows_come_from_fallback_or_refuse(self):
        olds, old_eps = _ps_servers(3, fill=lambda s: float(s + 1))
        news, new_eps = _ps_servers(2, fill=lambda s: 0.0)
        try:
            olds[1].shutdown()
            with pytest.raises(RuntimeError, match="refusing to lose"):
                reshard_tables(old_eps, new_eps, ["emb"])
            fb = np.full((12, 4), 42.0, np.float32)
            report = reshard_tables(old_eps, new_eps, ["emb"], epoch=6,
                                    fallback={"emb": fb})
            assert report == {"emb": 4}          # rows 1,4,7,10 recovered
            tab = news[0].tables["emb"]._table
            np.testing.assert_allclose(tab[1], 42.0)
            np.testing.assert_allclose(tab[0], 1.0)
        finally:
            for s in olds[:1] + olds[2:] + news:
                s.shutdown()

    def test_adagrad_g2_recovered_from_fallback_or_reset(self):
        olds, old_eps = _ps_servers(3, table_optimizer="adagrad")
        for s, srv in enumerate(olds):           # distinct accumulators
            srv.tables["emb"]._g2[:] = float(s + 1)
        news, new_eps = _ps_servers(2, table_optimizer="adagrad")
        try:
            olds[1].shutdown()
            fb = {"table": np.full((12, 4), 9.0, np.float32),
                  "g2": np.full((12,), 7.0, np.float32)}
            reshard_tables(old_eps, new_eps, ["emb"], fallback={"emb": fb})
            g2 = news[0].tables["emb"]._g2
            assert g2[1] == 7.0                  # dead-owned: from fallback
            assert g2[0] == 1.0 and g2[2] == 3.0  # surviving owners kept
            # no g2 in the fallback: recovered rows reset to fresh-row 0
            reshard_tables(old_eps, new_eps, ["emb"],
                           fallback={"emb": fb["table"]})
            g2 = news[0].tables["emb"]._g2
            assert g2[1] == 0.0 and g2[0] == 1.0
        finally:
            for s in olds[:1] + olds[2:] + news:
                s.shutdown()


# ---------------------------------------------------------------------------
# checkpoint: world-size metadata + resilient membership signal
# ---------------------------------------------------------------------------

class _MLP(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(6, 12)
        self.fc2 = nn.Linear(12, 3)

    def forward(self, x):
        return self.fc2(paddle.nn.functional.relu(self.fc1(x)))


def _model_loss(model, x, y):
    return paddle.nn.functional.cross_entropy(model(x), y).mean()


def _mk_step(seed=0):
    paddle.seed(seed)
    model = _MLP()
    opt = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                             parameters=model.parameters())
    return TrainStep(model, _model_loss, opt, donate=False)


def _batch(seed=0, n=8):
    rng = np.random.default_rng(seed)
    return (paddle.to_tensor(rng.standard_normal((n, 6)).astype("float32")),
            paddle.to_tensor(rng.integers(0, 3, size=(n,)).astype("int64")))


class TestWorldSizeMeta:
    def test_save_records_world_size_and_meta_reader(self, tmp_path):
        from paddle_tpu.distributed.checkpoint import (checkpoint_meta,
                                                       save_train_state)
        step = _mk_step()
        step(*_batch())
        d = str(tmp_path / "ck")
        save_train_state(step, d, global_step=9, world_size=4)
        meta = checkpoint_meta(d)
        assert meta["step"] == 9 and meta["world_size"] == 4

    def test_epoch_range_threads_world_size(self, tmp_path):
        from paddle_tpu.distributed.checkpoint import checkpoint_meta
        step = _mk_step()
        step(*_batch())
        ck = str(tmp_path / "acp")
        r = TrainEpochRange(5, "job", train_step=step, checkpoint_dir=ck,
                            world_size=4)
        r.save_checkpoint(1)
        slot, epoch = latest_checkpoint(ck)
        assert epoch == 1
        assert checkpoint_meta(slot)["world_size"] == 4
        # restore into a DIFFERENT world size: params land regardless
        step3 = _mk_step(seed=1)
        r3 = TrainEpochRange(5, "job", train_step=step3,
                             checkpoint_dir=ck, world_size=3)
        assert r3.restored_epoch == 1
        for (n, p), (_, q) in zip(step.model.named_parameters(),
                                  step3.model.named_parameters()):
            np.testing.assert_array_equal(np.asarray(p._data),
                                          np.asarray(q._data))

    def test_latest_checkpoint_none_when_uncommitted(self, tmp_path):
        assert latest_checkpoint(str(tmp_path / "nothing")) is None


class TestMembershipSignal:
    def test_reform_resnapshots_restored_state(self, tmp_path):
        """After reform() restores the committed slot, the resilient
        snapshot must hold the RESTORED state — a NaN rollback on the
        first post-reform step must not undo the checkpoint restore."""
        from paddle_tpu.framework.resilient import ResilientTrainStep
        inner = _mk_step()
        res = ResilientTrainStep(inner)
        ck = str(tmp_path / "acp")
        r = TrainEpochRange(10, "job", train_step=inner,
                            checkpoint_dir=ck)
        res(*_batch())
        r.save_checkpoint(0)                     # committed state A
        committed = {n: np.asarray(p._data)
                     for n, p in inner.model.named_parameters()}
        res(*_batch(seed=1))                     # train on to state B
        store = DictStore(ttl=5.0)
        store.register("w0")
        rm = PaddleCloudRoleMaker(is_collective=True)
        epoch, _, _, restored = reform(store, rm, "w0", train_step=inner,
                                       checkpoint_dir=ck, resilient=res)
        assert restored == 0 and res.membership_epoch == epoch
        res.restore()                            # a post-reform rollback
        for n, p in inner.model.named_parameters():
            np.testing.assert_array_equal(np.asarray(p._data),
                                          committed[n])

    def test_membership_changed_snapshots_before_reform(self):
        from paddle_tpu.framework.resilient import ResilientTrainStep
        inner = _mk_step()
        step = ResilientTrainStep(inner)
        step(*_batch())
        step.membership_changed(epoch=5)
        assert step.membership_epoch == 5 and step.membership_events == 1
        good = {n: np.asarray(p._data)
                for n, p in inner.model.named_parameters()}
        # the re-form (or a later rollback) can now always get back to
        # the pre-re-form state, even if the layout mutation scribbles
        for _, p in inner.model.named_parameters():
            p._data = p._data * 0.0
        step.restore()
        for n, p in inner.model.named_parameters():
            np.testing.assert_array_equal(np.asarray(p._data), good[n])


# ---------------------------------------------------------------------------
# THE acceptance run: 4 -> 3 shrink to loss parity (+ grow-on-join)
# ---------------------------------------------------------------------------

def _stream(n_steps, B=12):
    rng = np.random.default_rng(7)
    return [(rng.standard_normal((B, 6)).astype("float32"),
             rng.integers(0, 3, size=(B,)).astype("int64"))
            for _ in range(n_steps)]


def _dp_step(model, opt, params, opt_states, X, Y, world, key):
    """One data-parallel step: each rank grads its contiguous shard of
    the SAME global batch, the weighted average equals the full-batch
    gradient — so runs at different world sizes are numerically parallel
    and the shrink run has a well-defined parity target."""
    n = X.shape[0]
    tot_g, tot_loss = None, 0.0
    for rank in range(world):
        sl = dp_shard(n, world, rank)
        w = (sl.stop - sl.start) / n

        def floss(p, sl=sl):
            loss, _ = functional_loss_call(
                model, _model_loss, p, {}, key,
                [jnp.asarray(X[sl]), jnp.asarray(Y[sl])])
            return loss

        loss, g = jax.value_and_grad(floss)(params)
        tot_loss += w * float(loss)
        scaled = jax.tree_util.tree_map(lambda a: w * a, g)
        tot_g = scaled if tot_g is None else jax.tree_util.tree_map(
            jnp.add, tot_g, scaled)
    new_p, new_s = apply_functional_update(
        opt, tot_g, params, opt_states, jnp.float32(opt.get_lr()))
    return new_p, new_s, tot_loss


def _run_elastic_job(world0, total_steps, ck_dir, ttl=3.5,
                     lease_fault_nth=None, join_at=None):
    """Deterministic in-process elastic data-parallel job.  Fake clock,
    lockstep workers, commits every 2nd step through the two-slot
    protocol; a lost lease stalls the collective until the sweep bumps
    the epoch, then the survivors reform() — refresh roles, restore the
    latest committed slot, resume at the new world size."""
    clock = _Clock()
    store = DictStore(ttl=ttl, clock=clock)
    paddle.seed(0)
    model = _MLP()
    opt = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                             parameters=model.parameters())
    container = TrainStep(model, _model_loss, opt, donate=False)
    params = {n: p._data for n, p in model.named_parameters()}
    opt_states = opt.functional_init_states(params)
    container._opt_states = opt_states
    epoch_range = TrainEpochRange(total_steps, "elastic-job",
                                  train_step=container,
                                  checkpoint_dir=ck_dir,
                                  world_size=world0)
    rm = PaddleCloudRoleMaker(is_collective=True)
    stream = _stream(total_steps)
    ctxs = {}
    for i in range(world0):
        w = f"w{i}"
        # renew_interval=0: one renewal per step keeps the elastic.lease
        # chaos schedule's call counting deterministic (nth targets a
        # specific worker's renewal at a specific step)
        ctxs[w] = ElasticWorkerContext(store, w, endpoint=f"h{i}:1",
                                       renew_interval=0.0)
        ctxs[w].join()
    for ctx in ctxs.values():
        ctx.resync()
    if lease_fault_nth is not None:
        chaos.arm("elastic.lease", mode="error", nth=lease_fault_nth,
                  n_times=1)
    dead, losses = set(), []
    reforms = stalls = recomputed = 0
    t, guard = 0, 0
    while t < total_steps:
        guard += 1
        assert guard < 40 * total_steps, "elastic sim failed to converge"
        clock.advance(1.0)
        store.sweep()
        if join_at is not None and t >= join_at and "wj" not in ctxs:
            ctxs["wj"] = ElasticWorkerContext(store, "wj", endpoint="hj:1",
                                              renew_interval=0.0)
            ctxs["wj"].join()                    # grow-on-join
        members = store.members()
        actives = [w for w in members if w not in dead]
        assert actives, "everyone lost their lease"
        if ctxs[actives[0]].membership_changed():
            for w in actives:
                store.renew(w)
            epoch, _, world, restored = reform(
                store, rm, actives[0], train_step=container,
                checkpoint_dir=ck_dir)
            for w in actives:
                ctxs[w].resync(epoch)
            params = {n: p._data for n, p in model.named_parameters()}
            opt_states = container._opt_states
            new_t = 0 if restored is None else restored + 1
            recomputed += t - new_t
            t = new_t
            reforms += 1
            continue
        if set(actives) != set(members):
            # a peer died but its lease has not expired yet: the
            # collective step cannot complete — renew and wait for the
            # sweep to bump the epoch
            for w in actives:
                store.renew(w)
            stalls += 1
            continue
        world = len(members)
        X, Y = stream[t]
        key = jax.random.PRNGKey(1000 + t)
        params, opt_states, loss = _dp_step(
            model, opt, params, opt_states, X, Y, world, key)
        losses.append(loss)
        for w in list(actives):
            try:
                ctxs[w].step_done(t)
            except (chaos.InjectedFault, LeaseExpired):
                dead.add(w)                      # this worker just died
        if t % 2 == 0:
            for n_, p_ in model.named_parameters():
                p_._data = params[n_]
            container._opt_states = opt_states
            epoch_range.save_checkpoint(t)
        t += 1
    chaos.disarm("elastic.lease")
    return {"losses": losses, "params": {k: np.asarray(v)
                                         for k, v in params.items()},
            "reforms": reforms, "stalls": stalls,
            "recomputed": recomputed,
            "world": len(store.members()), "epoch": store.epoch()}


class TestShrinkToSurvive:
    def test_clean_runs_world_sizes_numerically_parallel(self, tmp_path):
        r4 = _run_elastic_job(4, 6, str(tmp_path / "a"))
        r3 = _run_elastic_job(3, 6, str(tmp_path / "b"))
        assert r4["reforms"] == r3["reforms"] == 0
        np.testing.assert_allclose(r4["losses"], r3["losses"], rtol=1e-4)

    def test_lease_fault_shrinks_4_to_3_with_loss_parity(self, tmp_path):
        """THE acceptance criterion: with an ``elastic.lease`` fault
        injected, the 4-worker job loses w3's renewal at step 3, the
        lease expires under the fake clock, the epoch bumps, survivors
        re-form (refresh + restore the latest committed slot) and the
        shrunk 3-worker job reaches the same final loss as a clean
        3-worker run."""
        # renew call order is deterministic: 4 per full step, so call 16
        # is w3's renewal at the end of step 3
        shrunk = _run_elastic_job(4, 10, str(tmp_path / "shrunk"),
                                  lease_fault_nth=16)
        clean = _run_elastic_job(3, 10, str(tmp_path / "clean"))
        assert shrunk["reforms"] == 1
        assert shrunk["stalls"] >= 1             # collective stalled
        assert shrunk["world"] == 3              # shrink-to-survive
        assert shrunk["recomputed"] >= 1         # resumed from the slot
        # epoch history: 4 joins + 1 lease expiry
        assert shrunk["epoch"] == 5
        np.testing.assert_allclose(shrunk["losses"][-1],
                                   clean["losses"][-1], rtol=1e-4)
        for k in clean["params"]:
            np.testing.assert_allclose(shrunk["params"][k],
                                       clean["params"][k], rtol=1e-4,
                                       atol=1e-6)

    def test_grow_on_join_reforms_to_larger_world(self, tmp_path):
        grown = _run_elastic_job(3, 10, str(tmp_path / "grown"),
                                 join_at=5)
        clean4 = _run_elastic_job(4, 10, str(tmp_path / "clean4"))
        assert grown["reforms"] == 1
        assert grown["world"] == 4               # grow-on-join
        np.testing.assert_allclose(grown["losses"][-1],
                                   clean4["losses"][-1], rtol=1e-4)


# ---------------------------------------------------------------------------
# launch CLI: elastic store end-to-end (children are plain scripts)
# ---------------------------------------------------------------------------

class TestElasticLaunch:
    def test_crash_restart_through_elastic_agent(self, tmp_path):
        marker = tmp_path / "count"
        script = tmp_path / "train.py"
        script.write_text(
            "import os, sys\n"
            "assert os.environ['PADDLE_ELASTIC_WORKER_ID']\n"
            "assert os.path.basename(os.environ['PADDLE_ELASTIC_STORE'])"
            " == 'rendezvous.json'\n"
            f"p = {str(marker)!r}\n"
            "n = int(open(p).read()) if os.path.exists(p) else 0\n"
            "open(p, 'w').write(str(n + 1))\n"
            "sys.exit(1 if n == 0 else 0)\n")
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--elastic_store", str(tmp_path / "es"),
             "--elastic_retries", "1", "--restart_backoff", "0.1",
             "--log_dir", str(tmp_path / "log"), str(script)],
            cwd=str(tmp_path), capture_output=True, text=True, timeout=120,
            env=dict(os.environ, PYTHONPATH=_REPO))
        assert r.returncode == 0, r.stderr
        assert marker.read_text() == "2"
        assert "restart_scheduled" in r.stderr

    def test_ps_mode_membership_holds_trainers_only(self, tmp_path):
        """PS servers are supervised but must never join the rendezvous
        membership — a server ranked into the data-parallel world would
        silently skew dp sharding for every refreshed trainer."""
        script = tmp_path / "ps.py"
        script.write_text("import os\nprint(os.environ['TRAINING_ROLE'])\n")
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--server_num", "2", "--worker_num", "2",
             "--elastic_store", str(tmp_path / "es"),
             "--log_dir", str(tmp_path / "log"), str(script)],
            cwd=str(tmp_path), capture_output=True, text=True, timeout=120,
            env=dict(os.environ, PYTHONPATH=_REPO))
        assert r.returncode == 0, r.stderr
        store = FileStore(str(tmp_path / "es" / "rendezvous.json"),
                          ttl=60.0)
        # 2 trainer joins + 2 clean leaves = epoch 4; had the servers
        # been members too, their joins/leaves would show in the epoch
        assert store.epoch() == 4 and store.members() == []
        assert "server-0" not in r.stderr.replace("serverlog", "")


# ---------------------------------------------------------------------------
# the real thing: SIGKILL a worker process mid-epoch (slow)
# ---------------------------------------------------------------------------

_SIGKILL_WORKER = """
import json, sys, time
from paddle_tpu.distributed.elastic import (ElasticWorkerContext,
                                            FileStore, LeaseExpired)
from paddle_tpu.distributed.fleet.role_maker import PaddleCloudRoleMaker

store_path, wid, out, expected = (sys.argv[1], sys.argv[2], sys.argv[3],
                                  int(sys.argv[4]))
store = FileStore(store_path, ttl=1.5)
ctx = ElasticWorkerContext(store, wid, endpoint=wid + ":0")
ctx.join()
deadline = time.time() + 60
while len(store.members()) < expected:          # wait for full world
    if time.time() > deadline:
        sys.exit(5)
    time.sleep(0.05)
    store.renew(wid)
ctx.resync()
print("FORMED", flush=True)
rm = PaddleCloudRoleMaker(is_collective=True)
step = 0
while time.time() < deadline:
    time.sleep(0.1)
    store.sweep()                               # leaderless expiry
    if ctx.membership_changed():
        rm.refresh(store=store, worker_id=wid)
        json.dump({"epoch": store.epoch(), "world": rm.worker_num(),
                   "rank": rm.worker_index()}, open(out, "w"))
        sys.exit(0)
    try:
        ctx.step_done(step)
    except (LeaseExpired, OSError):
        sys.exit(3)
    step += 1
sys.exit(4)
"""


@pytest.mark.slow
class TestSigkillReform:
    def test_sigkill_worker_mid_epoch_survivors_reform(self, tmp_path):
        store_path = str(tmp_path / "rdv.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
        procs = {}
        outs = {}
        try:
            for i in range(3):
                wid = f"w{i}"
                outs[wid] = str(tmp_path / f"{wid}.json")
                procs[wid] = subprocess.Popen(
                    [sys.executable, "-c", _SIGKILL_WORKER, store_path,
                     wid, outs[wid], "3"],
                    stdout=subprocess.PIPE, text=True, env=env,
                    cwd=_REPO)
            for wid, p in procs.items():
                assert p.stdout.readline().strip() == "FORMED", wid
            time.sleep(0.5)                      # mid-epoch
            procs["w1"].send_signal(signal.SIGKILL)
            for wid in ("w0", "w2"):
                assert procs[wid].wait(timeout=60) == 0, wid
            for wid in ("w0", "w2"):
                res = json.load(open(outs[wid]))
                assert res["world"] == 2         # shrank to the survivors
                assert res["epoch"] == 4         # 3 joins + 1 expiry
            ranks = {json.load(open(outs[w]))["rank"]
                     for w in ("w0", "w2")}
            assert ranks == {0, 1}               # re-ranked densely
            store = FileStore(store_path, ttl=1.5)
            assert store.members() == ["w0", "w2"]
        finally:
            for p in procs.values():
                if p.poll() is None:
                    p.kill()
                    p.wait(timeout=10)


# ---------------------------------------------------------------------------
# straggler score staleness (collector worker_ttl idiom, read-time)
# ---------------------------------------------------------------------------

class TestStragglerStaleness:
    """note_stragglers only records; every read (straggler_view /
    stragglers / straggler_overdue / enforce_straggler_policy) drops
    scores older than ``straggler_ttl`` or belonging to an evicted
    worker AT READ TIME — a dead worker's frozen score can never drive
    a shrink."""

    def _agent(self, names, clock, ttl=5.0, **kw):
        store = DictStore(ttl=60.0, clock=clock)
        handles = []
        for n in names:
            store.register(n)
            h = LocalHandle(n, lambda stop: stop.wait(10.0))
            h.start()
            handles.append(h)
        return store, ElasticAgent(store, handles, clock=clock,
                                   straggler_ttl=ttl, **kw)

    def test_scores_expire_at_read_time(self):
        clock = _Clock()
        _, agent = self._agent(["a", "b"], clock, ttl=5.0)
        try:
            agent.note_stragglers({"a": 2.0, "b": 1.0}, flagged=["a"])
            assert agent.straggler_view() == {"a": 2.0, "b": 1.0}
            assert agent.stragglers() == ["a"]
            clock.advance(5.1)
            assert agent.straggler_view() == {}
            assert agent.stragglers() == []
            assert agent.straggler_overdue(0.0) == []
            # the raw last-report dict is untouched — only reads filter
            assert agent.straggler_scores == {"a": 2.0, "b": 1.0}
        finally:
            for h in agent.handles:
                h.kill()

    def test_unknown_or_evicted_worker_never_drives_policy(self):
        clock = _Clock()
        _, agent = self._agent(["a"], clock)
        try:
            # "ghost" was never a member the agent could act on
            agent.note_stragglers({"a": 3.0, "ghost": 9.0},
                                  flagged=["a", "ghost"])
            assert "ghost" not in agent.straggler_view()
            assert agent.stragglers() == ["a"]
            agent._gone.add("a")        # evicted between report + read
            assert agent.stragglers() == []
            assert agent.enforce_straggler_policy(0.0) == []
        finally:
            for h in agent.handles:
                h.kill()

    def test_overdue_requires_continuous_flagging(self):
        clock = _Clock()
        _, agent = self._agent(["a"], clock, ttl=60.0)
        try:
            agent.note_stragglers({"a": 3.0}, flagged=["a"])
            assert agent.straggler_overdue(10.0) == []
            clock.advance(6.0)
            agent.note_stragglers({"a": 3.0}, flagged=["a"])
            assert agent.straggler_overdue(10.0) == []      # 6s < 10s
            clock.advance(5.0)
            agent.note_stragglers({"a": 3.0}, flagged=["a"])
            assert agent.straggler_overdue(10.0) == ["a"]   # 11s
            # one recovered report resets the continuous-flag clock
            agent.note_stragglers({"a": 0.5}, flagged=[])
            clock.advance(1.0)
            agent.note_stragglers({"a": 3.0}, flagged=["a"])
            assert agent.straggler_overdue(10.0) == []
        finally:
            for h in agent.handles:
                h.kill()

    def test_enforce_kills_then_shrinks_past_deadline(self):
        from paddle_tpu.framework.observability import flight
        flight.clear()
        clock = _Clock()
        _, agent = self._agent(["a", "b"], clock, ttl=60.0,
                               elastic_retries=0, min_world=1)
        try:
            agent.note_stragglers({"a": 4.0, "b": 1.0}, flagged=["a"])
            clock.advance(30.0)
            agent.note_stragglers({"a": 4.0, "b": 1.0}, flagged=["a"])
            evs = agent.enforce_straggler_policy(20.0)
            names = [(e[0], e[1]) for e in evs]
            assert ("straggler_killed", "a") in names
            assert ("shrunk", "a") in names
            assert not agent._by_name("a").alive()
            # the straggler's state is consumed: enforcing again no-ops
            assert agent.enforce_straggler_policy(0.0) == []
            assert agent.stragglers() == []
            kinds = [e["kind"] for e in flight.recent(30)]
            assert "elastic.straggler_killed" in kinds
        finally:
            for h in agent.handles:
                h.kill()

    def test_enforce_replaces_while_budget_lasts(self):
        clock = _Clock()
        _, agent = self._agent(["a", "b"], clock, ttl=60.0,
                               elastic_retries=1, min_world=1)
        try:
            agent.note_stragglers({"a": 4.0}, flagged=["a"])
            clock.advance(30.0)
            agent.note_stragglers({"a": 4.0}, flagged=["a"])
            evs = agent.enforce_straggler_policy(20.0)
            assert [(e[0], e[1]) for e in evs] == \
                [("straggler_killed", "a"), ("restart_scheduled", "a")]
        finally:
            for h in agent.handles:
                h.kill()
