"""Postmortem plane — incident capture bundles + deterministic replay.

Every observe/analyze plane in this repo ends at a flight event: a
``train.nan_skip`` names the first bad leaf, a ``parity.divergence``
names the first divergent one — and then the step's inputs, rng
stream, and pre-step state are gone, so *reproducing* the flagged step
means rerunning the whole job.  The reference's own postmortem story is
the same log-line dead end (FLAGS_check_nan_inf prints and aborts).
This module closes the loop:

* **ring** — with ``FLAGS_incident`` armed, :func:`maybe_note` (hooked
  at the head of ResilientTrainStep / PSTrainStep) keeps the last
  ``FLAGS_incident_ring`` steps of host-copied step inputs (batch
  arrays or PS pulled-row ids), rng state (a pure read — the stream is
  never perturbed), the chaos registry's mid-sequence schedule
  (:func:`chaos.arm_state`), and the pre-step training state.  All
  host-only reads: the armed trajectory is bitwise identical to the
  disarmed one, and disarmed the hook is one flag lookup — no extra
  jit outputs, signature-cache keys byte-identical to the seed.

* **capture** — a subscribed flight kind firing
  (``FLAGS_incident_kinds``; default ``train.nan_skip``,
  ``health.anomaly``, ``numerics.scale_collapse``,
  ``parity.divergence``, ``pallas.divergence``) assembles a crash-safe
  **incident bundle**
  under ``FLAGS_incident_dir``: the input ring, an inline params/opt
  snapshot below ``FLAGS_incident_state_cap_mb`` (or a ``{root,
  generation}`` ref to the newest verified checkpoint generation),
  ``flags.overrides()``, the chaos schedule, ``monitor.snapshot()``,
  the flight tail since the ring began, the blame split when a tracer
  is live, and per-step trajectory hashes (``parity.leaf_hash_host``)
  for first-divergence bisection.  Every file lands tmp+rename with a
  crc32 stamp and the ``COMMIT`` marker is written strictly last —
  :func:`verify_bundle` refuses a torn directory exactly like the
  PR-18 generation walk refuses a torn checkpoint.  The triggering
  event is stamped with the bundle's monotonic ``incident`` id (the
  attr round-trips through ``flight.recent()/since()``), a
  ``kind=incident`` RunLedger record indexes it for ``perf_report
  incidents``, and a bounded notice queue feeds the collector push
  payload.  Capture NEVER raises: the ``incident.capture`` chaos point
  plus a swallow-and-count guard (``incident_capture_errors_total``)
  pin the watcher-never-crashes-the-watched contract.

* **replay** — ``tools/replay.py <bundle>`` re-executes the ring
  standalone: restore the recorded state, re-arm flags + the
  mid-sequence chaos stream, re-feed the ringed inputs through the
  real step surface, and gate that the recorded signal reproduces
  (same ``first_bad_leaf``); ``--bisect`` re-executes with chaos
  DISARMED and walks the recorded trajectory hashes to the first step
  whose clean re-execution diverges — the poisoned step, by number.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
import zlib
from typing import Any, Dict, List, Optional

import numpy as np

from paddle_tpu.framework import chaos, monitor
from paddle_tpu.framework.flags import flag

__all__ = ["DEFAULT_KINDS", "enabled", "subscribed_kinds", "incident_dir",
           "IncidentRecorder", "recorder", "maybe_note", "install",
           "uninstall", "set_program", "reset", "verify_bundle",
           "read_manifest", "load_ring_entry", "state_tree_of_prestate",
           "hash_state_tree", "hash_step_state", "drain_notices",
           "train_surface", "BUNDLE_PREFIX", "MANIFEST_NAME",
           "COMMIT_NAME"]

SCHEMA_VERSION = 1
BUNDLE_PREFIX = "incident_"
MANIFEST_NAME = "manifest.json"
COMMIT_NAME = "COMMIT"
STATE_DIRNAME = "state"

#: the built-in subscription — every plane that names a step/leaf
#: worth reproducing offline
DEFAULT_KINDS = ("train.nan_skip", "health.anomaly",
                 "numerics.scale_collapse", "parity.divergence",
                 "pallas.divergence")


def enabled() -> bool:
    """True when the postmortem plane is armed (``FLAGS_incident``)."""
    return bool(flag("incident"))


def subscribed_kinds() -> frozenset:
    """Flight kinds that trigger capture (``FLAGS_incident_kinds``,
    comma-separated; empty = :data:`DEFAULT_KINDS`)."""
    raw = str(flag("incident_kinds") or "").strip()
    if not raw:
        return frozenset(DEFAULT_KINDS)
    return frozenset(k.strip() for k in raw.split(",") if k.strip())


def incident_dir() -> str:
    """Bundle root (``FLAGS_incident_dir``; empty = ``incidents`` under
    the current directory)."""
    return str(flag("incident_dir") or "") or os.path.join(
        os.getcwd(), "incidents")


# ---------------------------------------------------------------------------
# state helpers (shared with tools/replay.py)
# ---------------------------------------------------------------------------


def train_surface(step):
    """Unwrap to the innermost object with the TrainStep surface
    (``model``/``optimizer``/``_opt_states``): a ResilientTrainStep
    ring-notes itself, but state capture/restore and trajectory hashing
    happen on the wrapped step."""
    cur = step
    for _ in range(4):
        if getattr(cur, "model", None) is not None:
            return cur
        nxt = getattr(cur, "step", None)
        if nxt is None:
            return cur
        cur = nxt
    return cur


def _host_prestate(step) -> Optional[dict]:
    """Host copy of a TrainStep-surface object's full training state in
    the exact ``_capture_train_state`` shape, so the inline bundle state
    restores through the ordinary ``checkpoint.load_train_state`` path."""
    import jax.tree_util as jtu
    step = train_surface(step)
    model = getattr(step, "model", None)
    opt = getattr(step, "optimizer", None)
    if model is None or opt is None:
        return None
    states = getattr(step, "_opt_states", None)
    return {
        "params": {n: np.asarray(p._data)
                   for n, p in model.named_parameters()},
        "buffers": {n: np.asarray(b._data)
                    for n, b in model.named_buffers() if b is not None},
        "opt_states": jtu.tree_map(np.asarray, states)
        if states is not None else {},
        "global_step": np.int64(getattr(opt, "_global_step", 0)),
    }


def state_tree_of_prestate(pre_state: dict) -> Dict[str, np.ndarray]:
    """Flat name->array view of a :func:`_host_prestate` dict using the
    parity plane's leaf naming (params by name, ``opt<keystr>`` for
    optimizer leaves) — both halves of a bisection name the same leaf."""
    import jax.tree_util as jtu
    tree = dict(pre_state.get("params") or {})
    states = pre_state.get("opt_states")
    if states:
        flat, _ = jtu.tree_flatten_with_path(states)
        for path, leaf in flat:
            if hasattr(leaf, "shape"):
                tree["opt" + jtu.keystr(path)] = leaf
    return tree


def hash_state_tree(tree: Dict[str, Any]) -> Dict[str, int]:
    """Per-leaf host hash of a flat name->array tree
    (:func:`paddle_tpu.parallel.parity.leaf_hash_host`)."""
    from paddle_tpu.parallel.parity import leaf_hash_host
    return {n: leaf_hash_host(tree[n]) for n in sorted(tree)}


def hash_step_state(step) -> Dict[str, int]:
    """Per-leaf host hash of a LIVE step's params + opt-state leaves."""
    from paddle_tpu.parallel.parity import _state_tree
    return hash_state_tree(_state_tree(train_surface(step)))


def _prestate_nbytes(pre_state: dict) -> int:
    import jax.tree_util as jtu
    total = 0
    for leaf in jtu.tree_leaves(pre_state):
        total += getattr(leaf, "nbytes", 0)
    return total


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------


class IncidentRecorder:
    """Ring of recent step context + the capture listener.

    One process-wide instance (:data:`recorder`); the ring is rebuilt
    lazily from ``FLAGS_incident_ring`` at first armed note.  All
    mutation happens under one lock; capture itself runs under a
    thread-local reentrancy guard (capture fires flight events — the
    chaos trip, ledger write errors — that must not recurse into a
    second capture)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ring: Optional[collections.deque] = None
        self._installed = False
        self._tls = threading.local()
        self._program: Optional[dict] = None
        self.notices: collections.deque = collections.deque(maxlen=64)
        self.last_bundle: Optional[str] = None
        self.captured_total = 0

    # -- ring ----------------------------------------------------------------
    def _buf(self) -> collections.deque:
        if self._ring is None:
            self._ring = collections.deque(
                maxlen=max(1, int(flag("incident_ring"))))
        return self._ring

    def note(self, step, inputs) -> None:
        """Record one step's replay context (armed path; callers gate on
        :func:`enabled`).  Host-only reads: input copies, a pure rng
        state read, the chaos schedule, and the pre-step state — the
        watched trajectory is never perturbed."""
        from paddle_tpu.framework.observability import flight
        from paddle_tpu.tensor.random import get_rng_state
        ins = []
        for x in inputs:
            data = getattr(x, "_data", None)
            if data is not None:
                ins.append(("tensor", np.asarray(data)))
            else:
                ins.append(("array", np.asarray(x)))
        surf = train_surface(step)
        entry = {
            "step": int(getattr(getattr(surf, "optimizer", None),
                                "_global_step", 0)),
            "inputs": ins,
            "rng": np.asarray(get_rng_state()),
            "chaos": chaos.arm_state(),
            "flight_seq": flight.last_seq(),
            "pre_state": _host_prestate(step),
            "step_obj": step,
        }
        with self._lock:
            self._buf().append(entry)

    # -- program descriptor --------------------------------------------------
    def set_program(self, builder: str, **kwargs) -> None:
        """Register how a replay rebuilds this process's step surface:
        ``builder`` is a ``"module:function"`` ref returning the step
        object when called with ``**kwargs`` (JSON-able).  Stamped into
        every bundle so ``tools/replay.py`` is standalone."""
        self._program = {"builder": str(builder), "kwargs": dict(kwargs)}

    # -- listener ------------------------------------------------------------
    def install(self) -> None:
        """Subscribe the capture listener to the flight recorder
        (idempotent)."""
        from paddle_tpu.framework.observability import flight
        with self._lock:
            if self._installed:
                return
            self._installed = True
        flight.add_listener(self._on_event)

    def uninstall(self) -> None:
        from paddle_tpu.framework.observability import flight
        with self._lock:
            if not self._installed:
                return
            self._installed = False
        flight.remove_listener(self._on_event)

    def _on_event(self, ev: dict) -> None:
        """The flight listener: subscribed kind → capture a bundle and
        stamp the LIVE event dict with the incident id (the attr
        round-trips through ``recent()/since()``).  NEVER raises."""
        if getattr(self._tls, "in_capture", False):
            return
        try:
            if not enabled() or ev.get("kind") not in subscribed_kinds():
                return
        except Exception:          # noqa: BLE001 — flags gone mid-teardown
            return
        self._tls.in_capture = True
        try:
            chaos.fault_point("incident.capture",
                              meta={"kind": ev.get("kind")})
            bundle = self._capture(ev)
            if bundle is not None:
                ev["attrs"]["incident"] = bundle["incident_id"]
        except Exception:          # noqa: BLE001 — swallow-and-count: the
            # postmortem recorder must never crash the run it records
            monitor.stat_add("incident_capture_errors_total")
        finally:
            self._tls.in_capture = False

    # -- capture -------------------------------------------------------------
    def _claim_bundle_dir(self, root: str):
        """Monotonic incident id from a directory scan, claimed by an
        exclusive mkdir (two racing captures get distinct ids)."""
        os.makedirs(root, exist_ok=True)
        nxt = 1
        for name in os.listdir(root):
            if name.startswith(BUNDLE_PREFIX):
                try:
                    nxt = max(nxt, int(name[len(BUNDLE_PREFIX):]) + 1)
                except ValueError:
                    continue
        for iid in range(nxt, nxt + 1000):
            path = os.path.join(root, f"{BUNDLE_PREFIX}{iid:06d}")
            try:
                os.makedirs(path)
                return iid, path
            except FileExistsError:
                continue
        raise RuntimeError(f"cannot claim an incident dir under {root}")

    def _capture(self, ev: dict) -> Optional[dict]:
        from paddle_tpu.distributed import checkpoint
        from paddle_tpu.framework.observability import flight
        with self._lock:
            entries = list(self._buf())
        iid, path = self._claim_bundle_dir(incident_dir())

        # 1) state: inline below the cap (standalone replay), else a ref
        # to the newest verified checkpoint generation
        state_rec: Dict[str, Any] = {}
        cap_bytes = float(flag("incident_state_cap_mb")) * 1e6
        pre = entries[0]["pre_state"] if entries else None
        if pre is not None and 0 < _prestate_nbytes(pre) <= cap_bytes:
            sdir = os.path.join(path, STATE_DIRNAME)
            checkpoint.save_sharded(pre, sdir,
                                    step=int(pre["global_step"]))
            checkpoint.write_commit(sdir,
                                    generation=int(pre["global_step"]))
            state_rec = {"inline": True, "dir": STATE_DIRNAME}
        else:
            gen_ref = self._generation_ref(entries)
            state_rec = {"inline": False, "ref": gen_ref}

        # 2) the input ring: one crc-stamped .npy per array, tmp+rename
        ring_meta: List[dict] = []
        for i, e in enumerate(entries):
            files = []
            for j, (kind, arr) in enumerate(e["inputs"]):
                fname = f"ring_e{i}_in{j}.npy"
                crc, nbytes = checkpoint._atomic_save(path, fname, arr)
                files.append({"file": fname, "kind": kind,
                              "crc32": crc, "bytes": nbytes})
            rng_f = f"ring_e{i}_rng.npy"
            rng_crc, rng_b = checkpoint._atomic_save(path, rng_f, e["rng"])
            ring_meta.append({
                "step": e["step"], "inputs": files,
                "rng": {"file": rng_f, "crc32": rng_crc, "bytes": rng_b},
                "chaos": e["chaos"], "flight_seq": e["flight_seq"]})

        # 3) trajectory hashes for --bisect: entry i's post-state IS
        # entry i+1's pre-state; the LAST entry's post-state is the live
        # state right now — capture runs inside flight.record, BEFORE
        # any rollback/restore, so it sees the state the signal saw
        trajectory: List[dict] = []
        for i, e in enumerate(entries):
            if e["pre_state"] is None:
                trajectory.append({"step": e["step"], "pre_hashes": None})
            else:
                trajectory.append({
                    "step": e["step"],
                    "pre_hashes": hash_state_tree(
                        state_tree_of_prestate(e["pre_state"]))})
        post_hashes = None
        if entries and entries[-1].get("step_obj") is not None:
            try:
                post_hashes = hash_step_state(entries[-1]["step_obj"])
            except Exception:      # noqa: BLE001 — hash is best-effort
                post_hashes = None

        # 4) manifest (crc-stamped into COMMIT) + COMMIT strictly last
        since = entries[0]["flight_seq"] if entries else 0
        manifest: Dict[str, Any] = {
            "schema_version": SCHEMA_VERSION,
            "incident_id": iid,
            "ts": time.time(),
            "worker": {"pid": os.getpid(),
                       "host": _hostname(),
                       "worker": os.environ.get("PADDLE_TRAINER_ID")},
            "event": {"kind": ev.get("kind"),
                      "severity": ev.get("severity"),
                      "seq": ev.get("seq"),
                      "attrs": _jsonable(ev.get("attrs", {}))},
            "flags_overrides": _flags_overrides(),
            "chaos": entries[0]["chaos"] if entries else chaos.arm_state(),
            "chaos_at_capture": chaos.arm_state(),
            "monitor": _monitor_snapshot(),
            "flight_tail": _jsonable(flight.since(since)),
            "program": self._program,
            "state": state_rec,
            "ring": ring_meta,
            "trajectory": trajectory,
            "post_hashes": post_hashes,
        }
        blame = _blame_window()
        if blame is not None:
            manifest["blame"] = blame
        from paddle_tpu.distributed.fleet.utils.fs import LocalFS
        payload = json.dumps(manifest, default=str)
        LocalFS().atomic_write(os.path.join(path, MANIFEST_NAME), payload)
        LocalFS().atomic_write(
            os.path.join(path, COMMIT_NAME),
            json.dumps({"incident_id": iid, "time": time.time(),
                        "manifest_crc32":
                            zlib.crc32(payload.encode()) & 0xFFFFFFFF}))

        monitor.stat_add("incident_captured_total")
        self.captured_total += 1
        self.last_bundle = path
        notice = {"id": iid, "kind": ev.get("kind"),
                  "step": entries[-1]["step"] if entries else None,
                  "bundle": path,
                  "worker": manifest["worker"]["worker"]
                  or manifest["worker"]["host"]}
        self.notices.append(notice)
        flight.record("incident.captured", severity="info",
                      incident=iid, trigger=ev.get("kind"), bundle=path)
        self._ledger_record(ev, manifest, path)
        return manifest

    def _generation_ref(self, entries) -> Optional[dict]:
        """{root, generation} of the newest verified checkpoint
        generation, when a durable manager is discoverable from the
        ringed step (attach_durable wiring); None otherwise."""
        step = entries[-1].get("step_obj") if entries else None
        mgr = None
        cur = step
        for _ in range(3):
            if cur is None:
                break
            mgr = getattr(cur, "_durable", None)
            if mgr is not None:
                break
            cur = getattr(cur, "step", None)
        if mgr is None:
            return None
        try:
            gen = mgr.latest_verified(deep=False)
        except Exception:          # noqa: BLE001
            return None
        if gen is None:
            return None
        return {"root": os.path.abspath(mgr.root), "generation": int(gen)}

    def _ledger_record(self, ev: dict, manifest: dict, path: str) -> None:
        """kind=incident RunLedger record (best-effort; the ledger's own
        append never raises)."""
        from paddle_tpu.framework import runlog
        lpath = runlog.default_ledger_path()
        if not lpath:
            return
        attrs = manifest["event"].get("attrs") or {}
        info = {"id": manifest["incident_id"],
                "kind": manifest["event"].get("kind"),
                "step": manifest["ring"][-1]["step"]
                if manifest["ring"] else None,
                "first_bad_leaf": attrs.get("first_bad_leaf"),
                "bundle": os.path.abspath(path),
                "worker": manifest["worker"].get("worker")
                or manifest["worker"].get("host")}
        rec = runlog.capture(kind="incident",
                             label=manifest["event"].get("kind"),
                             include_snapshot=False,
                             extra={"incident": info})
        runlog.RunLedger(lpath).append(rec)

    def reset(self) -> None:
        """Clear the ring + notices (tests); the listener stays."""
        with self._lock:
            self._ring = None
            self.notices.clear()
            self.last_bundle = None


def _hostname() -> str:
    import socket
    try:
        return socket.gethostname()
    except Exception:              # noqa: BLE001
        return "unknown"


def _flags_overrides() -> dict:
    from paddle_tpu.framework import flags as _flags
    try:
        return _jsonable(_flags.overrides())
    except Exception:              # noqa: BLE001
        return {}


def _monitor_snapshot() -> Optional[dict]:
    try:
        return _jsonable(monitor.snapshot())
    except Exception:              # noqa: BLE001
        return None


def _blame_window() -> Optional[dict]:
    """Blame split + span window when a tracer is live (FLAGS_trace_dir)
    — best-effort: a torn trace must not fail a capture."""
    try:
        tdir = str(flag("trace_dir") or "")
        if not tdir:
            return None
        from paddle_tpu.framework import blame as _blame
        res = _blame.compute_blame(_blame.load_trace_dir(tdir))
        if not res.get("n_steps"):
            return None
        return {"n_steps": res["n_steps"], "totals_ms": res["totals_ms"],
                "per_step_ms": res["per_step_ms"],
                "top_category": res["top_category"]}
    except Exception:              # noqa: BLE001
        return None


def _jsonable(obj):
    """Round-trip through JSON with default=str so a numpy scalar or an
    exotic attr can never tear the manifest write."""
    return json.loads(json.dumps(obj, default=str))


# ---------------------------------------------------------------------------
# module-level facade
# ---------------------------------------------------------------------------

#: process-wide recorder
recorder = IncidentRecorder()


def maybe_note(step, inputs) -> None:
    """The one-line hook the step classes call at the head of each step:
    one flag lookup when disarmed; armed, ring-record this step's replay
    context and (lazily, once) subscribe the capture listener."""
    if not enabled():
        return
    recorder.install()
    try:
        recorder.note(step, inputs)
    except Exception:              # noqa: BLE001 — swallow-and-count: the
        # ring must never perturb or crash the watched step
        monitor.stat_add("incident_capture_errors_total")


def install() -> None:
    """Subscribe the capture listener without waiting for a first armed
    step — for processes whose subscribed kinds can fire before any
    ringed step."""
    recorder.install()


def uninstall() -> None:
    recorder.uninstall()


def set_program(builder: str, **kwargs) -> None:
    """See :meth:`IncidentRecorder.set_program`."""
    recorder.set_program(builder, **kwargs)


def reset() -> None:
    """Clear ring + notices (tests)."""
    recorder.reset()


def drain_notices() -> List[dict]:
    """Incident notices ({id, kind, step, bundle, worker}) accumulated
    since process start, bounded — what the collector client ships in
    its push payload (cumulative, not destructive: a dropped push must
    not lose a notice; the server dedups by id)."""
    return list(recorder.notices)


# ---------------------------------------------------------------------------
# bundle readers (shared with tools/replay.py + tests)
# ---------------------------------------------------------------------------


def verify_bundle(path: str) -> List[dict]:
    """Fsck one bundle directory; ``[]`` = intact.  Mirrors
    ``checkpoint.verify_checkpoint``: a missing/torn COMMIT, a manifest
    whose crc disagrees with the COMMIT stamp, a missing or corrupt ring
    file, or a torn inline state dir each yield a ``{file, reason}``
    problem — replay refuses a bundle with any."""
    problems: List[dict] = []
    commit_path = os.path.join(path, COMMIT_NAME)
    try:
        with open(commit_path) as f:
            commit = json.load(f)
    except (OSError, ValueError):
        return [{"file": COMMIT_NAME, "reason": "missing"}]
    try:
        with open(os.path.join(path, MANIFEST_NAME), "rb") as f:
            raw = f.read()
    except OSError:
        return [{"file": MANIFEST_NAME, "reason": "missing"}]
    want = commit.get("manifest_crc32")
    if want is not None and (zlib.crc32(raw) & 0xFFFFFFFF) != want:
        return [{"file": MANIFEST_NAME, "reason": "crc_mismatch"}]
    try:
        manifest = json.loads(raw.decode("utf-8"))
    except ValueError:
        return [{"file": MANIFEST_NAME, "reason": "bad_manifest"}]
    for e in manifest.get("ring", []):
        for rec in list(e.get("inputs", [])) + [e.get("rng")]:
            if not rec:
                continue
            fp = os.path.join(path, rec["file"])
            try:
                with open(fp, "rb") as f:
                    data = f.read()
            except OSError:
                problems.append({"file": rec["file"], "reason": "missing"})
                continue
            if len(data) != rec.get("bytes"):
                problems.append({"file": rec["file"],
                                 "reason": "truncated"})
            elif (zlib.crc32(data) & 0xFFFFFFFF) != rec.get("crc32"):
                problems.append({"file": rec["file"],
                                 "reason": "crc_mismatch"})
    state = manifest.get("state") or {}
    if state.get("inline"):
        from paddle_tpu.distributed import checkpoint
        sdir = os.path.join(path, state.get("dir") or STATE_DIRNAME)
        if not checkpoint.is_committed(sdir):
            problems.append({"file": state.get("dir") or STATE_DIRNAME,
                             "reason": "state_uncommitted"})
        else:
            problems.extend(checkpoint.verify_checkpoint(sdir, deep=True))
    return problems


def read_manifest(path: str) -> dict:
    with open(os.path.join(path, MANIFEST_NAME)) as f:
        return json.load(f)


def load_ring_entry(path: str, entry: dict) -> dict:
    """Materialize one manifest ring entry: inputs (tensor-kind arrays
    re-wrapped lazily by the caller), rng state, chaos schedule."""
    inputs = []
    for rec in entry.get("inputs", []):
        inputs.append((rec.get("kind", "array"),
                       np.load(os.path.join(path, rec["file"]))))
    rng = np.load(os.path.join(path, entry["rng"]["file"])) \
        if entry.get("rng") else None
    return {"step": entry.get("step"), "inputs": inputs, "rng": rng,
            "chaos": entry.get("chaos")}
