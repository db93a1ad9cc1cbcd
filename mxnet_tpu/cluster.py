"""Cluster control plane: one ``cluster.json`` spec, one reconciling loop.

The reference's distributed story is mediated by ONE ps-lite *scheduler*
role that registers nodes, brokers barriers, and survives worker churn
(SURVEY §L7; dmlc-tracker launchers). This module is that role's
TPU-native redesign: a single declarative spec over every process the
stack knows how to supervise — training gangs (:mod:`mxnet_tpu.elastic`
gang semantics), serving fleets (per-slot semantics +
:mod:`mxnet_tpu.serving.fleet` routing/autoscaling decision cores), and
the model bus (:mod:`mxnet_tpu.modelbus` wiring) — interpreted by one
reconciling supervisor loop:

    observe   heartbeat / telemetry / announce shards + the process
              table (pid + /proc start-ticks), per role
    diff      desired (spec) vs actual (observation + world state)
    act       spawn / drain / restart / scale / adopt / gc — every
              action routed through the exit-code ladder
              (:mod:`mxnet_tpu.preempt`) and per-slot restart budgets

**Crash-safety is the headline.** All world state — generation
counters, slot tables, restart ledgers, the last actions — lives in ONE
atomic-write record (``world.json`` under the run dir, written with the
same pid+thread-ident tmp + fsync + ``os.replace`` seam every other
protocol writer uses). SIGKILLing the supervisor and restarting it is a
non-event: the new incarnation loads ``world.json``, **re-adopts**
running workers, and reconciles without killing or restarting anything
healthy.

Re-adoption rules (in order, per recorded slot):

1. recorded pid alive AND its current ``/proc/<pid>/stat`` start-ticks
   equal the recorded start-ticks -> **adopt** (the slot keeps its id,
   generation and restart count; observation continues via pid +
   heartbeat/announce since an adopted process is not our child);
2. pid alive but start-ticks differ -> **stale pid reuse**: the worker
   died during the outage and the OS re-issued its pid — never adopt,
   classify like (3);
3. pid dead -> classify the exit from on-disk evidence: a final
   announce / heartbeat in ``draining``/``drained`` state means a
   graceful drain (exit 75); anything else is a hard loss (exit 137
   equivalent) — restartable, charged to the slot's budget like any
   other ladder exit.

``cluster.json`` spec grammar::

    {"cluster": "<name>",
     "roles": {
       "<role>": {"kind": "trainer-gang",
                  "command": ["python", "train.py", ...],
                  "workers": 2,            # census (gang size)
                  "max_restarts": 5,       # role-wide budget
                  "backoff": 0.5, "backoff_cap": 30.0,
                  "grace": 10.0,           # SIGTERM->SIGKILL deadline
                  "dead_after": 0.0,       # heartbeat-silence kill (0 off)
                  "coordinator_port": 9357,
                  "publish_to": "<bus role>"},      # bus wiring
       "<role>": {"kind": "model-bus",
                  "dir": null,             # default <run_dir>/<role>
                  "keep": 8,               # gc: keep newest N (0 = all)
                  "model": "net"},         # lineage root
       "<role>": {"kind": "serving-fleet",
                  "model_dir": "models",   # serving.json dir (spec-rel)
                  "workers": 2,
                  "min": 1, "max": 4,      # autoscale bounds (min==max off)
                  "policy": "least_loaded",
                  "restarts": 5,           # per-slot budget
                  "backoff": 0.5, "backoff_cap": 30.0,
                  "grace": 10.0, "dead_after": 0.0,
                  "http_port": 0,          # router port (0 = ephemeral)
                  "subscribe_to": "<bus role>",     # bus wiring
                  "lineage": {"model": "net", "min_version": 0}}}}

State-record format (``world.json``, one atomic record)::

    {"cluster": name, "incarnation": N,
     "supervisor": {"pid":, "start_ticks":, "started":, "state":},
     "generation": {role: N},
     "next_slot": {role: N},              # serving slot ids never reused
     "slots": {role: {slot: {"pid":, "start_ticks":, "generation":,
                             "state":, "restarts":, "spawned":,
                             "adopted":, "last_exit":,
                             "backoff_until":}}},
     "ledger": {role: {"restarts_total":, "slots": {slot: N},
                       "budget":, "exhausted":}},
     "actions": [last 64 {"t":, "kind":, "role":, "slot":, "reason":}],
     "router": {role: {"port":, "url":}},
     "updated": t_wall}

Fault/observability wiring: the observe and act halves of every tick
run under :func:`mxnet_tpu.watchdog.sync` spans (``cluster.observe`` /
``cluster.act``) so a wedged reconcile pass hits the watchdog ladder
like every other blocking span, and hit the matching
:func:`mxnet_tpu.faults.point` injection points (plus the
``supervisor.act`` alias every action routes through).  Scrapes export
``mxtpu_cluster_*`` gauges; every action and adoption lands in the
flight ring (``cluster.*`` events); ``tools/diagnose.py`` renders the
"Cluster" report from the spec + world record; ``tools/launch.py
--cluster <spec>`` is the CLI entry.

:class:`mxnet_tpu.elastic.GangSupervisor` /
:class:`~mxnet_tpu.elastic.ServingSupervisor` remain as the
single-role compat adapters over this module's primitives
(:func:`atomic_record`, :func:`next_backoff`, :class:`RestartLedger`,
the env helpers) — their decision cores are the same policies the
reconciler's role drivers apply, reached through one world model here.
"""
from __future__ import annotations

import json
import os
import signal as _signal
import subprocess
import sys
import threading
import time
import weakref

from . import faults as _faults
from . import log as _log
from . import preempt as _preempt
from . import watchdog as _watchdog
from .telemetry import flight as _flight

__all__ = [
    "ClusterError", "ClusterSupervisor", "WorldState", "RestartLedger",
    "load_spec", "validate_spec", "atomic_record", "env_float",
    "env_int", "next_backoff", "pid_alive", "proc_start_ticks",
    "adoption_verdict", "classify_outage_exit", "live_supervisors",
    "ROLE_KINDS", "WORLD_FILE", "SPEC_FILE", "describe",
]

_logger = _log.get_logger("mxnet_tpu.cluster")

ROLE_KINDS = ("trainer-gang", "serving-fleet", "model-bus")
WORLD_FILE = "world.json"
SPEC_FILE = "cluster.json"

#: exits that charge a restart instead of failing the role — the ladder
RESTARTABLE_EXITS = frozenset({_preempt.DRAIN_EXIT_CODE,          # 75
                               _preempt.PEERLOST_EXIT_CODE,       # 76
                               _watchdog.ABORT_EXIT_CODE,         # 86
                               137,                               # SIGKILL
                               255})                              # ssh lost


class ClusterError(RuntimeError):
    """Malformed cluster spec or an unreconcilable world."""


# ------------------------------------------------------ shared primitives --
# The process-plane primitives every supervisor in the stack shares.
# elastic.GangSupervisor / elastic.ServingSupervisor delegate here (PR 19
# refactor) — one implementation of the atomic-record seam, the backoff
# curve and the env grammar helpers instead of three.

def atomic_record(path, obj):
    """Atomically publish a JSON record: unique tmp (pid + thread ident —
    concurrent writers never share a tmp name), fsync, ``os.replace``.
    Readers see the old or the new record, never a torn one.

    Deliberately NOT checkpoint.atomic_write: control-plane records must
    stay writable while the ``ckpt.write`` fault point is armed.
    """
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=1, sort_keys=True, default=str)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def env_float(name, default):
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def env_int(name, default):
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def next_backoff(backoff, cap, restarts_used):
    """The shared restart-delay curve: ``backoff`` doubling per restart,
    capped — restart #1 waits ``backoff``, #2 ``2*backoff``, ..."""
    if restarts_used <= 0:
        return 0.0
    return min(float(cap), float(backoff) * 2 ** (restarts_used - 1))


def pid_alive(pid):
    """Is `pid` a live process we may signal? (EPERM counts as alive;
    a zombie does NOT — it has exited for every supervision purpose,
    and an adopted slot's zombie may linger un-reaped because its
    original parent is gone and we never held a waitpid handle.)"""
    if not pid or pid <= 0:
        return False
    try:
        os.kill(int(pid), 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    except OSError:
        return False
    try:
        with open(f"/proc/{int(pid)}/stat") as f:
            stat = f.read()
        if stat[stat.rindex(")") + 2:].split(" ", 1)[0] == "Z":
            return False
    except (OSError, ValueError):
        pass  # no procfs: the kill(0) answer stands
    return True


def proc_start_ticks(pid):
    """The process start time in clock ticks from ``/proc/<pid>/stat``
    (field 22) — the pid-reuse discriminator: a recycled pid never
    shares its predecessor's start-ticks. None when unreadable (process
    gone, or a platform without procfs — adoption then needs heartbeat
    evidence)."""
    try:
        with open(f"/proc/{int(pid)}/stat", "rb") as f:
            data = f.read()
        # comm may contain spaces/parens: parse after the LAST ')'
        rest = data[data.rindex(b")") + 2:].split()
        return int(rest[19])  # field 22, 1-based, after pid+comm
    except (OSError, ValueError, IndexError):
        return None


def adoption_verdict(rec, now=None):
    """Can the slot described by world record `rec` be re-adopted by a
    restarted supervisor? Returns ``(verdict, why)`` with verdict one of
    ``adopt`` / ``stale-pid`` / ``dead``.

    * ``adopt``: recorded pid is alive and its current start-ticks match
      the recorded ones (when the record has none — procfs was
      unreadable at spawn — a live pid alone is trusted only if the
      record is younger than 60s, else it is treated as stale);
    * ``stale-pid``: pid alive but start-ticks differ — the pid was
      recycled by the OS during the outage;
    * ``dead``: pid gone.
    """
    now = time.time() if now is None else now
    pid = rec.get("pid")
    if not pid_alive(pid):
        return "dead", f"pid {pid} gone"
    ticks = proc_start_ticks(pid)
    want = rec.get("start_ticks")
    if want is None:
        if now - float(rec.get("spawned") or 0) <= 60.0:
            return "adopt", f"pid {pid} alive (no recorded start-ticks)"
        return "stale-pid", (f"pid {pid} alive but the record has no "
                             "start-ticks and is too old to trust")
    if ticks == want:
        return "adopt", f"pid {pid} alive, start-ticks {ticks} match"
    return "stale-pid", (f"pid {pid} alive but start-ticks {ticks} != "
                         f"recorded {want} (pid reused)")


def _scavenged_record(slot, ev):
    """Synthesize a world slot record from a worker's own on-disk
    evidence (gang heartbeat / serving announce) when the world record
    itself was torn. The evidence carries the worker's pid and
    start-ticks (written by the worker, so exact); ``spawned`` is
    stamped "now" so a legacy record without start-ticks still lands in
    adoption_verdict's short live-pid trust window."""
    return {"slot": int(slot), "generation": int(ev.get("generation", 1)),
            "pid": ev.get("pid"), "start_ticks": ev.get("start_ticks"),
            "spawned": time.time(), "state": "running", "restarts": 0}


def classify_outage_exit(rec, evidence):
    """Classify the exit of a worker that died while the supervisor was
    down — there is no waitpid status to read, only on-disk evidence.
    `evidence` is the slot's freshest record (final announce or
    heartbeat, possibly None). Returns a canonical ladder exit code:

    * announce/heartbeat state ``drained``/``draining`` -> 75 (a
      graceful drain completed or was in flight);
    * anything else -> 137 (hard loss during the outage: indistin-
      guishable from SIGKILL, and restartable exactly like one).
    """
    state = (evidence or {}).get("state")
    if state in ("drained", "draining"):
        return _preempt.DRAIN_EXIT_CODE
    return 137


# ----------------------------------------------------------- restart ledger --

class RestartLedger:
    """Budgeted restart accounting, role-wide or per-slot, persisted in
    the world record. ``charge`` answers whether the budget still covers
    one more restart and how long to back off (the shared curve)."""

    def __init__(self, budget, backoff, backoff_cap, per_slot=False):
        self.budget = int(budget)
        self.backoff = float(backoff)
        self.backoff_cap = float(backoff_cap)
        self.per_slot = bool(per_slot)
        self.restarts_total = 0
        self.slots = {}            # str(slot) -> restarts
        self.exhausted = False

    def used(self, slot=None):
        if self.per_slot and slot is not None:
            return self.slots.get(str(slot), 0)
        return self.restarts_total

    def charge(self, slot=None, reason=""):
        """Charge one restart. Returns ``(allowed, delay_s)``; once the
        budget is exceeded ``allowed`` is False and the ledger latches
        ``exhausted``."""
        used = self.used(slot)
        if used >= self.budget:
            self.exhausted = True
            return False, 0.0
        self.restarts_total += 1
        if self.per_slot and slot is not None:
            self.slots[str(slot)] = self.slots.get(str(slot), 0) + 1
        return True, next_backoff(self.backoff, self.backoff_cap,
                                  self.used(slot))

    def as_dict(self):
        return {"budget": self.budget, "per_slot": self.per_slot,
                "restarts_total": self.restarts_total,
                "slots": dict(self.slots), "exhausted": self.exhausted}

    @classmethod
    def from_dict(cls, rec, budget, backoff, backoff_cap, per_slot):
        led = cls(budget, backoff, backoff_cap, per_slot)
        try:
            led.restarts_total = int(rec.get("restarts_total", 0))
            led.slots = {str(k): int(v)
                         for k, v in (rec.get("slots") or {}).items()}
            led.exhausted = bool(rec.get("exhausted"))
        except (TypeError, ValueError, AttributeError):
            pass
        return led


# ------------------------------------------------------------------- spec --

_GANG_DEFAULTS = {"workers": 1, "max_restarts": 5, "backoff": 0.5,
                  "backoff_cap": 30.0, "grace": 10.0, "dead_after": 0.0,
                  "coordinator_port": 9357, "publish_to": None,
                  "publish_model": None, "shrink_on_kill": False}
_SERVE_DEFAULTS = {"workers": None, "min": 1, "max": 4,
                   "policy": "least_loaded", "restarts": 5,
                   "backoff": 0.5, "backoff_cap": 30.0, "grace": 10.0,
                   "dead_after": 0.0, "http_port": 0, "warmup": True,
                   "subscribe_to": None, "lineage": None, "hosts": None}
_BUS_DEFAULTS = {"dir": None, "keep": 0, "model": None}

_ROLE_DEFAULTS = {"trainer-gang": _GANG_DEFAULTS,
                  "serving-fleet": _SERVE_DEFAULTS,
                  "model-bus": _BUS_DEFAULTS}


def validate_spec(obj, base_dir=None):
    """Validate + normalize a cluster spec dict (defaults filled, paths
    resolved against `base_dir`). Raises :class:`ClusterError` naming
    the offending role/field."""
    if not isinstance(obj, dict) or not isinstance(obj.get("roles"), dict) \
            or not obj["roles"]:
        raise ClusterError("cluster spec needs a non-empty 'roles' map")
    out = {"cluster": str(obj.get("cluster") or "cluster"), "roles": {}}
    buses = {n for n, r in obj["roles"].items()
             if isinstance(r, dict) and r.get("kind") == "model-bus"}
    for name, role in obj["roles"].items():
        if not isinstance(role, dict):
            raise ClusterError(f"role {name!r} must be an object")
        kind = role.get("kind")
        if kind not in ROLE_KINDS:
            raise ClusterError(f"role {name!r}: unknown kind {kind!r}; "
                               f"expected one of {ROLE_KINDS}")
        cfg = dict(_ROLE_DEFAULTS[kind])
        for key, val in role.items():
            if key == "kind":
                continue
            if key not in cfg and key not in ("command", "model_dir"):
                raise ClusterError(f"role {name!r}: unknown option "
                                   f"{key!r} for kind {kind!r}")
            cfg[key] = val
        cfg["kind"] = kind
        if kind == "trainer-gang":
            cmd = cfg.get("command")
            if not isinstance(cmd, list) or not cmd:
                raise ClusterError(f"role {name!r}: trainer-gang needs a "
                                   "non-empty 'command' list")
            cfg["command"] = [str(c) for c in cmd]
            if int(cfg["workers"]) < 1:
                raise ClusterError(f"role {name!r}: workers must be >= 1")
        if kind == "serving-fleet":
            mdir = cfg.get("model_dir")
            if not mdir:
                raise ClusterError(f"role {name!r}: serving-fleet needs "
                                   "'model_dir'")
            if base_dir and not os.path.isabs(mdir):
                mdir = os.path.join(base_dir, mdir)
            cfg["model_dir"] = os.fspath(mdir)
            if int(cfg["min"]) < 1 or int(cfg["max"]) < int(cfg["min"]):
                raise ClusterError(f"role {name!r}: need 1 <= min <= max")
            if cfg["workers"] is None:
                cfg["workers"] = int(cfg["min"])
            cfg["workers"] = min(max(int(cfg["workers"]),
                                     int(cfg["min"])), int(cfg["max"]))
            if cfg.get("hosts"):
                from .serving import fleet as _fleet_mod

                try:
                    cfg["hosts"] = _fleet_mod.normalize_hosts(
                        cfg["hosts"])
                except ValueError as e:
                    raise ClusterError(
                        f"role {name!r}: bad hosts: {e}") from e
        for key in ("publish_to", "subscribe_to"):
            target = cfg.get(key)
            if target is not None and target not in buses:
                raise ClusterError(
                    f"role {name!r}: {key} names {target!r}, which is "
                    f"not a model-bus role (buses: {sorted(buses)})")
        out["roles"][name] = cfg
    return out


def load_spec(path):
    """Load + validate ``cluster.json`` from `path` (relative model
    dirs resolve against the spec's directory)."""
    path = os.fspath(path)
    try:
        with open(path) as f:
            obj = json.load(f)
    except OSError as e:
        raise ClusterError(f"cannot read cluster spec {path!r}: {e}") from e
    except ValueError as e:
        raise ClusterError(f"malformed cluster spec {path!r}: {e}") from e
    return validate_spec(obj, base_dir=os.path.dirname(os.path.abspath(path)))


# ------------------------------------------------------------ world state --

_ACTION_KEEP = 64
_torn_warned = set()


class WorldState:
    """The supervisor's persistent world model: everything a restarted
    incarnation needs to re-adopt the cluster, in one atomic record."""

    def __init__(self, run_dir):
        self.run_dir = os.fspath(run_dir)
        self.path = os.path.join(self.run_dir, WORLD_FILE)
        self.cluster = None
        self.incarnation = 0
        self.supervisor = {}
        self.generation = {}       # role -> int
        self.next_slot = {}        # role -> int
        self.slots = {}            # role -> {str(slot): rec}
        self.ledger = {}           # role -> ledger dict
        self.actions = []
        self.router = {}           # role -> {"port":, "url":}
        self.torn = False          # last load saw a torn/partial record

    @classmethod
    def load(cls, run_dir):
        """Load ``world.json`` (fresh world when absent). A torn or
        truncated record — the SIGKILL landed mid-write before the
        atomic seam existed, or the file was hand-mangled — degrades to
        a fresh world with ``torn=True``: re-adoption then runs from
        live observation (heartbeats/announces) alone."""
        ws = cls(run_dir)
        try:
            with open(ws.path) as f:
                rec = json.load(f)   # concur: torn-ok
        except OSError:
            return ws
        except ValueError:
            ws.torn = True
            if ws.path not in _torn_warned:
                _torn_warned.add(ws.path)
                _logger.warning(
                    "cluster: torn world record at %s — rebuilding the "
                    "world from live observation", ws.path)
            return ws
        try:
            ws.cluster = rec.get("cluster")
            ws.incarnation = int(rec.get("incarnation", 0))
            ws.supervisor = dict(rec.get("supervisor") or {})
            ws.generation = {str(k): int(v) for k, v in
                             (rec.get("generation") or {}).items()}
            ws.next_slot = {str(k): int(v) for k, v in
                            (rec.get("next_slot") or {}).items()}
            ws.slots = {str(r): {str(s): dict(sr) for s, sr in t.items()}
                        for r, t in (rec.get("slots") or {}).items()}
            ws.ledger = {str(k): dict(v) for k, v in
                         (rec.get("ledger") or {}).items()}
            ws.actions = list(rec.get("actions") or [])[-_ACTION_KEEP:]
            ws.router = {str(k): dict(v) for k, v in
                         (rec.get("router") or {}).items()}
        except (TypeError, ValueError, AttributeError):
            ws.torn = True
        return ws

    def as_dict(self):
        return {"cluster": self.cluster, "incarnation": self.incarnation,
                "supervisor": self.supervisor,
                "generation": self.generation,
                "next_slot": self.next_slot, "slots": self.slots,
                "ledger": self.ledger,
                "actions": self.actions[-_ACTION_KEEP:],
                "router": self.router, "updated": time.time()}

    def save(self):
        try:
            atomic_record(self.path, self.as_dict())
        except OSError as e:
            _logger.warning("cluster: could not write world record: %s", e)

    def record_action(self, kind, role=None, slot=None, reason=None,
                      **extra):
        rec = {"t": time.time(), "kind": kind, "role": role,
               "slot": slot, "reason": reason}
        rec.update(extra)
        self.actions.append(rec)
        del self.actions[:-_ACTION_KEEP]
        _flight.rec(f"cluster.{kind}",
                    f"{role or '-'}" + (f"/s{slot}" if slot is not None
                                        else ""), reason)
        return rec


# ------------------------------------------------------------ role drivers --

class _Slot:
    """One supervised process: either our child (``proc`` set) or an
    adopted orphan (pid-only; observation via /proc + shards)."""

    __slots__ = ("slot", "generation", "proc", "pid", "start_ticks",
                 "spawned", "state", "restarts", "adopted", "last_exit",
                 "backoff_until", "drain_deadline", "reason")

    def __init__(self, slot, generation):
        self.slot = int(slot)
        self.generation = int(generation)
        self.proc = None
        self.pid = None
        self.start_ticks = None
        self.spawned = 0.0
        self.state = "starting"    # starting|running|draining|backoff|
        self.restarts = 0          # retired|failed
        self.adopted = False
        self.last_exit = None
        self.backoff_until = 0.0   # wall clock: survives restarts
        self.drain_deadline = None
        self.reason = None

    def as_record(self):
        return {"slot": self.slot, "generation": self.generation,
                "pid": self.pid, "start_ticks": self.start_ticks,
                "spawned": self.spawned, "state": self.state,
                "restarts": self.restarts, "adopted": self.adopted,
                "last_exit": self.last_exit,
                "backoff_until": self.backoff_until,
                "reason": self.reason}

    @classmethod
    def from_record(cls, rec):
        s = cls(rec.get("slot", 0), rec.get("generation", 1))
        s.pid = rec.get("pid")
        s.start_ticks = rec.get("start_ticks")
        s.spawned = float(rec.get("spawned") or 0.0)
        s.state = rec.get("state") or "running"
        s.restarts = int(rec.get("restarts") or 0)
        s.adopted = True
        s.last_exit = rec.get("last_exit")
        s.backoff_until = float(rec.get("backoff_until") or 0.0)
        s.reason = rec.get("reason")
        return s

    def alive(self):
        if self.proc is not None:
            return self.proc.poll() is None
        return pid_alive(self.pid)

    def exit_code(self, evidence=None):
        """Canonical exit code once dead: waitpid status for children,
        on-disk evidence classification for adopted orphans."""
        if self.proc is not None:
            return _preempt.canonical_exit(self.proc.poll())
        return classify_outage_exit({"pid": self.pid}, evidence)

    def signal(self, sig):
        if self.proc is not None:
            if self.proc.poll() is not None:
                return
            try:
                self.proc.send_signal(sig)
            except (ProcessLookupError, OSError):
                pass
            return
        # adopted: direct kill, guarded against pid reuse by start-ticks
        if not pid_alive(self.pid):
            return
        if self.start_ticks is not None \
                and proc_start_ticks(self.pid) != self.start_ticks:
            return
        try:
            os.kill(int(self.pid), sig)
        except (ProcessLookupError, OSError):
            pass


class _Role:
    """Shared slot-plane mechanics for a spec role: spawn / adopt /
    reap / budgeted restart. Policy (gang vs per-slot) lives in the
    subclasses; the supervisor owns the loop."""

    def __init__(self, sup, name, cfg):
        self.sup = sup
        self.name = name
        self.cfg = cfg
        self.slots = {}            # slot id -> _Slot
        self.generation = max(1, sup.world.generation.get(name, 1))
        self.next_slot = sup.world.next_slot.get(name, 0)
        self.state = "idle"        # idle|running|degraded|failed|done
        per_slot = cfg["kind"] == "serving-fleet"
        budget = cfg.get("restarts" if per_slot else "max_restarts", 5)
        self.ledger = RestartLedger.from_dict(
            sup.world.ledger.get(name) or {}, budget,
            cfg.get("backoff", 0.5), cfg.get("backoff_cap", 30.0),
            per_slot)
        self.dir = os.path.join(sup.run_dir, name)
        os.makedirs(self.dir, exist_ok=True)

    # -- persistence ------------------------------------------------------
    def publish(self):
        w = self.sup.world
        w.generation[self.name] = self.generation
        w.next_slot[self.name] = self.next_slot
        w.slots[self.name] = {str(s.slot): s.as_record()
                              for s in self.slots.values()}
        w.ledger[self.name] = self.ledger.as_dict()

    # -- process plane ----------------------------------------------------
    def _base_env(self, slot, generation):
        env = dict(os.environ)
        env.update(self.sup.extra_env)
        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        env["PYTHONPATH"] = pkg_root + os.pathsep + \
            env.get("PYTHONPATH", "")
        env["MXTPU_GANG_DIR"] = self.dir
        env["MXTPU_WORKER_ID"] = str(slot)
        env["MXTPU_GANG_GENERATION"] = str(generation)
        env["MXTPU_CLUSTER_DIR"] = self.sup.run_dir
        env.setdefault("MXNET_TPU_CRASH_DIR",
                       os.path.join(self.sup.run_dir, "crash"))
        env.setdefault("MXNET_TPU_PREEMPT_DIR", self.dir)
        env.setdefault("MXNET_TPU_PREEMPT", "1")
        return env

    def command_for(self, slot, generation):
        raise NotImplementedError

    def env_for(self, slot, generation):
        return self._base_env(slot, generation)

    def spawn(self, slot, generation, reason="spawn"):
        s = self.slots.get(slot)
        if s is None or s.state in ("retired", "failed"):
            s = _Slot(slot, generation)
            self.slots[slot] = s
        restarts = s.restarts
        s.__init__(slot, generation)
        s.restarts = restarts
        cmd = self.command_for(slot, generation)
        popen = self.sup.popen or subprocess.Popen
        s.proc = popen(cmd, env=self.env_for(slot, generation),
                       cwd=self.sup.cwd)
        s.pid = s.proc.pid
        s.start_ticks = proc_start_ticks(s.pid)
        s.spawned = time.time()
        s.state = "running"
        self.sup.world.record_action("spawn", self.name, slot, reason,
                                     pid=s.pid, generation=generation)
        return s

    def adopt_from(self, rec):
        """Re-adopt (or classify) one recorded slot on supervisor
        restart. Returns the verdict string."""
        verdict, why = adoption_verdict(rec)
        slot = int(rec.get("slot", 0))
        if rec.get("state") in ("retired", "failed"):
            s = _Slot.from_record(rec)
            self.slots[slot] = s
            return "kept"
        if verdict == "adopt":
            s = _Slot.from_record(rec)
            if s.start_ticks is None:
                s.start_ticks = proc_start_ticks(s.pid)
            self.slots[slot] = s
            self.sup.world.record_action("adopt", self.name, slot, why,
                                         pid=s.pid)
            _logger.info("cluster: %s/s%d re-adopted (%s)", self.name,
                         slot, why)
            return "adopt"
        # stale-pid or dead: classify the outage exit from evidence
        s = _Slot.from_record(rec)
        s.pid = None if verdict == "stale-pid" else s.pid
        code = classify_outage_exit(rec, self.evidence_for(slot))
        s.last_exit = code
        s.state = "exited-during-outage"
        self.slots[slot] = s
        self.sup.world.record_action(
            "outage-exit", self.name, slot,
            f"{why}; classified {code} "
            f"({_preempt.classify_exit(code)})", exit=code)
        return verdict

    def evidence_for(self, slot):
        """Freshest on-disk record for `slot` (role-specific)."""
        return None

    def scavenge(self):
        """``{slot: synthesized record}`` rebuilt from the workers' own
        on-disk evidence — the adoption source of last resort when the
        world record was torn (role-specific; default: nothing)."""
        return {}

    def drain_slot(self, slot, reason="drain"):
        s = self.slots.get(slot)
        if s is None:
            return
        if not s.alive():
            s.state = "retired"
            s.reason = reason
            return
        s.state = "draining"
        s.reason = reason
        s.drain_deadline = time.monotonic() + float(self.cfg["grace"])
        s.signal(_signal.SIGTERM)
        self.sup.world.record_action("drain", self.name, slot, reason,
                                     pid=s.pid)

    def escalate_drains(self):
        now = time.monotonic()
        for s in self.slots.values():
            if s.state == "draining" and s.drain_deadline is not None \
                    and now >= s.drain_deadline and s.alive():
                s.signal(_signal.SIGKILL)
                s.drain_deadline = now + 5.0
                self.sup.world.record_action(
                    "drain-kill", self.name, s.slot,
                    "grace expired", pid=s.pid)

    def stop(self, graceful=True):
        for slot, s in list(self.slots.items()):
            if s.alive():
                if graceful:
                    self.drain_slot(slot, reason="cluster stop")
                else:
                    s.signal(_signal.SIGKILL)

    def alive_count(self):
        return sum(1 for s in self.slots.values() if s.alive())

    def note_adopted(self):
        """Post-re-adoption hook (after generation/next_slot restore)."""

    # -- reconcile hooks (subclasses) -------------------------------------
    def observe(self, obs):
        raise NotImplementedError

    def reconcile(self, obs):
        raise NotImplementedError

    def describe(self):
        return {"kind": self.cfg["kind"], "state": self.state,
                "generation": self.generation,
                "slots": {str(s.slot): s.as_record()
                          for s in self.slots.values()},
                "ledger": self.ledger.as_dict()}


class _GangRole(_Role):
    """trainer-gang semantics: N rank slots, one generation — ANY ladder
    exit restarts the WHOLE gang at generation N+1 with a fresh
    coordinator epoch; a non-ladder exit is fatal for the role; the
    restart budget is role-wide."""

    def command_for(self, slot, generation):
        return list(self.cfg["command"])

    def env_for(self, slot, generation):
        env = self._base_env(slot, generation)
        port = int(self.cfg["coordinator_port"]) + generation - 1
        env["MXTPU_COORDINATOR"] = f"127.0.0.1:{port}"
        env["MXTPU_NUM_WORKERS"] = str(self.cfg["workers"])
        env["DMLC_NUM_WORKER"] = str(self.cfg["workers"])
        env["DMLC_WORKER_ID"] = str(slot)
        bus = self.cfg.get("publish_to")
        if bus:
            env["MXTPU_MODELBUS_DIR"] = self.sup.bus_dir(bus)
        return env

    def evidence_for(self, slot):
        from . import elastic as _elastic

        return _elastic.read_heartbeats(self.dir).get(slot)

    def scavenge(self):
        from . import elastic as _elastic

        dead_after = float(self.cfg["dead_after"])
        return {int(r): _scavenged_record(r, rec)
                for r, rec in _elastic.read_heartbeats(self.dir).items()
                if rec.get("age_s", 1e9) <= dead_after}

    def note_adopted(self):
        # a shrink survives the supervisor crash: the adopted slot table
        # at the current generation IS the census, not the spec's
        if not self.cfg.get("shrink_on_kill") or not self.slots:
            return
        cur = sum(1 for s in self.slots.values()
                  if s.generation == self.generation)
        if cur:
            self.cfg["workers"] = min(int(self.cfg["workers"]), cur)

    def observe(self, obs):
        from . import elastic as _elastic

        beats = _elastic.read_heartbeats(self.dir)
        exits = {}
        for s in self.slots.values():
            if s.state in ("running", "draining") and not s.alive():
                exits[s.slot] = s.exit_code(beats.get(s.slot))
        obs["roles"][self.name] = {
            "kind": "trainer-gang", "generation": self.generation,
            "alive": self.alive_count(), "desired": self.cfg["workers"],
            "heartbeats": {r: {"age_s": b.get("age_s"),
                               "steps": b.get("steps"),
                               "state": b.get("state")}
                           for r, b in beats.items()},
            "exits": exits}

    def reconcile(self, obs):
        role_obs = obs["roles"][self.name]
        actions = []
        if self.state in ("failed", "done"):
            return actions
        if not self.slots:
            actions.append({"kind": "gang-start", "role": self.name,
                            "reason": "initial spawn"})
            return actions
        exits = dict(role_obs["exits"])
        # record fresh exits on the slot table
        for slot, code in exits.items():
            s = self.slots.get(slot)
            if s is not None and s.state in ("running", "draining"):
                s.last_exit = code
                s.state = "exited"
                self.sup.world.record_action(
                    "exit", self.name, slot,
                    f"exit {code} ({_preempt.classify_exit(code)})",
                    exit=code)
        # outage-classified exits join the verdict
        for s in self.slots.values():
            if s.state == "exited-during-outage":
                exits[s.slot] = s.last_exit
                s.state = "exited"
        if not exits and all(s.state == "exited" or s.alive()
                             for s in self.slots.values()):
            exited = [s for s in self.slots.values()
                      if s.state == "exited"]
            if exited and len(exited) == len(self.slots):
                codes = [s.last_exit for s in exited]
                if all(c == 0 for c in codes):
                    actions.append({"kind": "gang-done",
                                    "role": self.name,
                                    "reason": "all ranks exited 0"})
                    return actions
        if exits:
            codes = list(exits.values())
            fatal = sorted(c for c in codes
                           if c not in RESTARTABLE_EXITS and c != 0)
            if fatal:
                actions.append({"kind": "gang-fail", "role": self.name,
                                "reason": f"fatal exit {fatal[0]} "
                                          "(non-ladder)",
                                "exit": fatal[0]})
            elif any(c in RESTARTABLE_EXITS for c in codes):
                worst = _preempt.most_severe(codes)
                actions.append({
                    "kind": "gang-restart", "role": self.name,
                    "reason": f"rank exits {sorted(exits.items())} "
                              f"({_preempt.classify_exit(worst)})",
                    "exit": worst})
        return actions

    def perform(self, action):
        kind = action["kind"]
        if kind == "gang-start":
            for rank in range(int(self.cfg["workers"])):
                self.spawn(rank, self.generation, reason="gang start")
            self.state = "running"
        elif kind == "gang-done":
            self.state = "done"
            self.sup.world.record_action("done", self.name,
                                         reason=action["reason"])
        elif kind == "gang-fail":
            self.state = "failed"
            self.stop(graceful=False)
            self.sup.world.record_action("fail", self.name,
                                         reason=action["reason"])
        elif kind == "gang-restart":
            allowed, delay = self.ledger.charge(reason=action["reason"])
            if not allowed:
                self.state = "failed"
                self.stop(graceful=False)
                self.sup.world.record_action(
                    "fail", self.name,
                    reason=f"restart budget exhausted "
                           f"({self.ledger.budget}); last: "
                           f"{action['reason']}")
                return
            if self.cfg.get("shrink_on_kill"):
                lost = sorted(s.slot for s in self.slots.values()
                              if s.last_exit in (137, 255))
                if lost:
                    census = int(self.cfg["workers"]) - len(lost)
                    if census < 1:
                        self.state = "failed"
                        self.stop(graceful=False)
                        self.sup.world.record_action(
                            "fail", self.name,
                            reason=f"shrink-on-kill lost every rank "
                                   f"({lost})")
                        return
                    self.cfg["workers"] = census
                    self.sup.world.record_action(
                        "shrink", self.name,
                        reason=f"dropped killed rank(s) {lost}; "
                               f"census {census}")
            # teardown survivors of the old generation, then respawn
            for s in self.slots.values():
                if s.alive():
                    s.signal(_signal.SIGTERM)
            deadline = time.monotonic() + float(self.cfg["grace"])
            while time.monotonic() < deadline \
                    and any(s.alive() for s in self.slots.values()):
                time.sleep(0.05)
            for s in self.slots.values():
                if s.alive():
                    s.signal(_signal.SIGKILL)
            if delay > 0:
                time.sleep(min(delay, 5.0))
            self.generation += 1
            self.slots.clear()
            for rank in range(int(self.cfg["workers"])):
                self.spawn(rank, self.generation,
                           reason=f"gang restart gen{self.generation}: "
                                  f"{action['reason']}")
            self.sup.world.record_action(
                "gang-restart", self.name,
                reason=action["reason"],
                generation=self.generation,
                restarts_used=self.ledger.restarts_total)


class _ServeRole(_Role):
    """serving-fleet semantics: per-slot restart with budget + backoff,
    deliberate drains retire, slot ids never reused; autoscaling and
    routing borrow :mod:`mxnet_tpu.serving.fleet`'s decision cores
    (Autoscaler / order_candidates / gate_ready / worker_metrics /
    the router front). The lifecycle half of ServingFleet, re-homed on
    the reconciler's slot plane."""

    def __init__(self, sup, name, cfg):
        super().__init__(sup, name, cfg)
        from .serving import fleet as _fleet_mod

        self._fleet_mod = _fleet_mod
        self.generation = max(1, self.generation)
        scfg = dict(_fleet_mod.DEFAULTS)
        scfg.update({"min": int(cfg["min"]), "max": int(cfg["max"]),
                     "policy": cfg["policy"],
                     "restarts": int(cfg["restarts"]),
                     "grace": float(cfg["grace"]),
                     "dead_after": float(cfg["dead_after"])})
        self.cfg_fleet = scfg
        # _RouterFront duck-types on fleet.cfg["timeout_ms"]
        self.cfg["timeout_ms"] = scfg["timeout_ms"]
        self._scaler = _fleet_mod.Autoscaler(scfg)
        self._ring = _fleet_mod.HashRing()
        self._rr = 0
        self._routable = []
        self._endpoints = {}
        self._suspect = {}
        self._counters = {"requests": 0, "completed": 0, "retries": 0,
                          "rejects": 0, "errors": 0}
        self._count_lock = threading.Lock()
        # multi-host placement: slot -> host is pure arithmetic
        # (slot % len(hosts)), so it survives a supervisor crash with
        # no extra world state
        hosts = cfg.get("hosts")
        if hosts and not isinstance(hosts[0], dict):
            hosts = _fleet_mod.normalize_hosts(hosts)
        self.hosts = hosts or None
        if self.hosts:
            for h in self.hosts:
                h["run_dir"] = os.path.join(self.dir,
                                            f"host-{h['name']}")
                os.makedirs(h["run_dir"], exist_ok=True)
        # hedged requests + straggler flags: same governor the
        # standalone ServingFleet router uses (duck-typed surface)
        self._hedge = _fleet_mod.HedgeGovernor(scfg, self._slot_locality)
        self._last_completed = None
        self._last_sample = {}
        self._router = None
        self.desired = int(cfg["workers"])
        prev = sup.world.slots.get(name) or {}
        if prev:
            # desired census survives the supervisor crash (autoscaler
            # decisions are world state, not spec state)
            live = [r for r in prev.values()
                    if r.get("state") in ("running", "starting",
                                          "draining")]
            if live:
                self.desired = min(max(len(live), int(cfg["min"])),
                                   int(cfg["max"]))

    # _RouterFront duck-type surface --------------------------------------
    def pick(self, model):
        self._rr += 1
        depths = {s: m.get("queue_depth") for s, m in
                  self._last_sample.get("per_worker", {}).items()}
        localities = None
        if self.hosts:
            localities = {s: self._slot_locality(s)
                          for s in self._routable}
        order = self._fleet_mod.order_candidates(
            self.cfg_fleet["policy"], model, self._routable,
            depths=depths, rr=self._rr, ring=self._ring,
            localities=localities,
            remote_penalty=self._hedge.remote_penalty())
        return self._hedge.reorder(order, self._rr)

    def endpoint(self, slot):
        return self._endpoints.get(slot)

    def mark_suspect(self, slot, why=""):
        self._suspect[slot] = time.monotonic() + 1.0
        self._routable = [s for s in self._routable if s != slot]
        _flight.rec("cluster.suspect", f"{self.name}/s{slot}", why)

    def _count(self, key, n=1):
        with self._count_lock:
            self._counters[key] = self._counters.get(key, 0) + n

    def note_latency(self, slot, ms):
        self._hedge.note(slot, ms)

    def hedge_plan(self, slot, candidates):
        return self._hedge.plan(slot, candidates, self.endpoint)

    def _count_hedge(self, outcome):
        self._hedge.count(outcome)

    def stats(self, light=False):
        out = {"name": self.name, "state": self.state,
               "generation": self.generation, "desired": self.desired,
               "ready": len(self._routable)}
        if not light:
            out.update(self._hedge.describe())
            if self.hosts:
                out["hosts"] = [
                    {"name": h["name"], "ssh": h["ssh"],
                     "locality": h["locality"],
                     "slots": sorted(s for s in self.slots
                                     if self._host_of(s) is h)}
                    for h in self.hosts]
        return out

    def models(self):
        from .serving import worker as _worker_mod

        anns = _worker_mod.read_workers(self.dir)
        for slot in self._routable:
            ann = anns.get(slot)
            if ann and ann.get("models"):
                return {"models": ann["models"],
                        "generation": ann.get("generation")}
        return {"models": [], "generation": self.generation}

    # ---------------------------------------------------------------------
    def start_router(self):
        if self._router is not None:
            return
        from .serving.fleet import _RouterFront

        want_port = int(self.cfg.get("http_port") or 0)
        recorded = (self.sup.world.router.get(self.name) or {}).get("port")
        port = want_port or int(recorded or 0)
        try:
            self._router = _RouterFront(self, port=port).start()
        except OSError:
            # recorded port still in TIME_WAIT-ish state: fall back to
            # an ephemeral port; world records the new one
            self._router = _RouterFront(self, port=0).start()
        self.sup.world.router[self.name] = {"port": self._router.port,
                                            "url": self._router.url}
        self.sup.world.record_action("router", self.name,
                                     reason=self._router.url)

    def close_router(self):
        if self._router is not None:
            try:
                self._router.close()
            except OSError:
                pass
            self._router = None

    def _host_of(self, slot):
        if not self.hosts:
            return None
        return self.hosts[int(slot) % len(self.hosts)]

    def _slot_locality(self, slot):
        h = self._host_of(slot)
        return h["locality"] if h else "local"

    def command_for(self, slot, generation):
        host = self._host_of(slot)
        cmd = [sys.executable, "-m", "mxnet_tpu.serving.worker",
               "--model-dir", self.cfg["model_dir"],
               "--slot", str(slot), "--generation", str(generation)]
        if host:
            cmd += ["--run-dir", host["run_dir"],
                    "--host", host["advertise"]]
        if not self.cfg.get("warmup", True):
            cmd.append("--no-warmup")
        if host and host["ssh"]:
            from . import elastic as _elastic_mod

            renv = self.env_for(slot, generation)
            renv["MXTPU_GANG_DIR"] = host["run_dir"]
            renv.update(host["env"])
            return _elastic_mod._ssh_argv(host["ssh"], renv, cmd,
                                          cwd=host["cwd"])
        return cmd

    def env_for(self, slot, generation):
        env = self._base_env(slot, generation)
        env.pop("MXTPU_COORDINATOR", None)
        env.setdefault("MXNET_TPU_GANG_BEAT", "0.5")
        env.setdefault("MXTPU_FLEET_DIR", self.dir)
        bus = self.cfg.get("subscribe_to")
        if bus:
            env["MXTPU_MODELBUS_DIR"] = self.sup.bus_dir(bus)
        host = self._host_of(slot)
        if host and not host["ssh"]:
            # local pseudo-host: announces, heartbeats and telemetry
            # shards land in the per-host subdir (merged at scrape)
            env["MXTPU_GANG_DIR"] = host["run_dir"]
            env["MXTPU_FLEET_DIR"] = host["run_dir"]
            env.update(host["env"])
        return env

    def evidence_for(self, slot):
        from .serving import worker as _worker_mod

        return _worker_mod.read_workers(self.dir).get(slot)

    def scavenge(self):
        from .serving import worker as _worker_mod

        return {int(s): _scavenged_record(s, ann)
                for s, ann in _worker_mod.read_workers(self.dir).items()
                if ann.get("state") != "drained"}

    def _gate(self, anns):
        """Routable slots: alive + announce-gated + pid-matching.
        pid equality is relaxed for ssh-placed slots: the announce pid
        is the remote worker's, our census pid is the ssh client's."""
        out = []
        for slot, s in self.slots.items():
            ann = anns.get(slot)
            host = self._host_of(slot)
            pid_ok = (ann or {}).get("pid") == s.pid \
                or bool(host and host["ssh"])
            if s.state in ("running", "starting") and s.alive() \
                    and self._fleet_mod.gate_ready(ann) \
                    and pid_ok \
                    and ann.get("generation") == s.generation:
                out.append(slot)
                self._endpoints[slot] = (ann.get("host", "127.0.0.1"),
                                         int(ann["port"]))
        return sorted(out)

    def observe(self, obs):
        from .serving import worker as _worker_mod

        anns = _worker_mod.read_workers(self.dir)
        exits = {}
        for s in self.slots.values():
            if s.state in ("running", "starting", "draining") \
                    and not s.alive():
                exits[s.slot] = s.exit_code(anns.get(s.slot))
        ready = self._gate(anns)
        now = time.monotonic()
        self._suspect = {k: t for k, t in self._suspect.items()
                         if t > now}
        self._routable = [s for s in ready if s not in self._suspect] \
            or ready
        self._hedge.update_stragglers(self._routable)
        if self.cfg_fleet["policy"] == "hash":
            self._ring.rebuild(self._routable)
        metrics = self._fleet_mod.worker_metrics(
            self.dir, slots=set(self.slots))
        obs["roles"][self.name] = {
            "kind": "serving-fleet", "generation": self.generation,
            "desired": self.desired, "ready": ready,
            "routable": list(self._routable), "exits": exits,
            "announces": {s: {"state": a.get("state"),
                              "ready": a.get("ready"),
                              "pending_compiles":
                                  a.get("pending_compiles")}
                          for s, a in anns.items()},
            "metrics": metrics}

    def _sample(self, metrics, now):
        per = {s: m for s, m in metrics.items()
               if m.get("generation") == self.generation}
        depths = [m["queue_depth"] for m in per.values()
                  if m.get("queue_depth") is not None]
        p99s = [m["p99_ms"] for m in per.values()
                if m.get("p99_ms") is not None]
        fills = [m["fill"] for m in per.values()
                 if m.get("fill") is not None]
        completed = sum(m.get("completed") or 0.0 for m in per.values())
        rps = None
        if self._last_completed is not None:
            t0, c0 = self._last_completed
            if now > t0:
                rps = max(0.0, (completed - c0) / (now - t0))
        self._last_completed = (now, completed)
        sample = {"queue_depth": max(depths) if depths else None,
                  "p99_ms": max(p99s) if p99s else None,
                  "fill": max(fills) if fills else None,
                  "rps": rps, "per_worker": per}
        self._last_sample = sample
        return sample

    def reconcile(self, obs):
        role_obs = obs["roles"][self.name]
        actions = []
        if self.state in ("failed", "done"):
            return actions
        if self.state == "idle":
            self.state = "running"
        # exits first: deliberate drains retire, the rest restart in
        # place on the slot's budget
        for slot, code in role_obs["exits"].items():
            s = self.slots.get(slot)
            if s is None:
                continue
            deliberate = s.state == "draining"
            s.last_exit = code
            if deliberate and code in (0, _preempt.DRAIN_EXIT_CODE):
                s.state = "retired"
                actions.append({"kind": "retired", "role": self.name,
                                "slot": slot, "reason": s.reason,
                                "exit": code})
            elif deliberate:
                s.state = "retired"
                actions.append({"kind": "retired", "role": self.name,
                                "slot": slot,
                                "reason": f"{s.reason} (killed)",
                                "exit": code})
            else:
                actions.append({"kind": "slot-restart",
                                "role": self.name, "slot": slot,
                                "reason": f"exit {code} "
                                f"({_preempt.classify_exit(code)})",
                                "exit": code})
        # outage-classified exits
        for s in list(self.slots.values()):
            if s.state == "exited-during-outage":
                code = s.last_exit
                if code in (0, _preempt.DRAIN_EXIT_CODE):
                    s.state = "retired"
                    actions.append({"kind": "retired",
                                    "role": self.name, "slot": s.slot,
                                    "reason": "drained during "
                                              "supervisor outage",
                                    "exit": code})
                else:
                    actions.append({"kind": "slot-restart",
                                    "role": self.name, "slot": s.slot,
                                    "reason": f"lost during supervisor "
                                    f"outage (classified {code})",
                                    "exit": code})
        # autoscale (decision core borrowed from serving.fleet)
        now = time.monotonic()
        sample = self._sample(role_obs["metrics"], now)
        if self.cfg_fleet["max"] > self.cfg_fleet["min"] \
                and self.state == "running":
            active = sum(1 for s in self.slots.values()
                         if s.state in ("running", "starting")
                         and s.generation == self.generation)
            direction, rec = self._scaler.decide(sample, active, now=now)
            if direction == "up":
                actions.append({"kind": "scale", "role": self.name,
                                "to": min(self.cfg_fleet["max"],
                                          active + 1),
                                "reason": f"autoscale up: "
                                          f"{rec['reason']}"})
            elif direction == "down":
                actions.append({"kind": "scale", "role": self.name,
                                "to": max(self.cfg_fleet["min"],
                                          active - 1),
                                "reason": f"autoscale down: "
                                          f"{rec['reason']}"})
        # census: spawn up to desired. Failed slots (budget exhausted)
        # degrade capacity — replacing them with fresh-budget slots
        # would turn an exhausted budget into an infinite restart storm
        active = [s for s in self.slots.values()
                  if s.state in ("running", "starting")
                  and s.generation == self.generation
                  and s.alive()]
        backoff_now = [s for s in self.slots.values()
                       if s.state == "backoff"]
        failed = [s for s in self.slots.values() if s.state == "failed"]
        missing = self.desired - len(active) - len(backoff_now) \
            - len(failed) \
            - sum(1 for a in actions if a["kind"] == "slot-restart")
        for _ in range(max(0, missing)):
            actions.append({"kind": "slot-spawn", "role": self.name,
                            "reason": "census below desired"})
        # backoff expiry -> respawn
        now_wall = time.time()
        for s in self.slots.values():
            if s.state == "backoff" and now_wall >= s.backoff_until:
                actions.append({"kind": "slot-respawn",
                                "role": self.name, "slot": s.slot,
                                "reason": "backoff elapsed"})
        return actions

    def perform(self, action):
        kind = action["kind"]
        if kind == "retired":
            self.sup.world.record_action(
                "retire", self.name, action["slot"],
                action["reason"], exit=action.get("exit"))
        elif kind == "slot-spawn":
            slot = self.next_slot
            self.next_slot += 1
            self.spawn(slot, self.generation, reason=action["reason"])
        elif kind == "slot-restart":
            slot = action["slot"]
            s = self.slots.get(slot)
            allowed, delay = self.ledger.charge(slot,
                                                reason=action["reason"])
            if not allowed:
                s.state = "failed"
                self.sup.world.record_action(
                    "slot-fail", self.name, slot,
                    f"budget exhausted ({self.ledger.budget}); last: "
                    f"{action['reason']}")
                return
            s.restarts += 1
            if delay > 0:
                s.state = "backoff"
                s.backoff_until = time.time() + delay
                self.sup.world.record_action(
                    "backoff", self.name, slot,
                    f"{action['reason']}; retry in {delay:g}s")
            else:
                self.spawn(slot, self.generation,
                           reason=action["reason"])
        elif kind == "slot-respawn":
            self.spawn(action["slot"], self.generation,
                       reason=action["reason"])
        elif kind == "scale":
            self.scale_to(int(action["to"]), action["reason"])

    def scale_to(self, n, reason):
        active = sorted(s.slot for s in self.slots.values()
                        if s.state in ("running", "starting")
                        and s.generation == self.generation)
        self.desired = n
        if n < len(active):
            for slot in active[n:]:
                self.drain_slot(slot, reason=f"scale-down ({reason})")
        self.sup.world.record_action("scale", self.name,
                                     reason=f"-> {n}: {reason}")

    def describe(self):
        out = super().describe()
        out.update({"desired": self.desired,
                    "routable": list(self._routable),
                    "router": dict(self._counters),
                    "url": self._router.url if self._router else None,
                    "autoscaler": self._scaler.describe()})
        return out


class _BusRole(_Role):
    """model-bus wiring: no processes — the reconciler ensures the bus
    directory exists, surfaces lineage (latest version / model /
    quarantines) in the world, and garbage-collects old versions
    (keeping every version a kept delta record still needs as its
    base)."""

    def __init__(self, sup, name, cfg):
        super().__init__(sup, name, cfg)
        if cfg.get("dir"):
            self.dir = cfg["dir"] if os.path.isabs(cfg["dir"]) \
                else os.path.join(sup.run_dir, cfg["dir"])
            os.makedirs(self.dir, exist_ok=True)
        self.state = "running"

    def command_for(self, slot, generation):
        raise ClusterError("model-bus roles spawn no processes")

    def observe(self, obs):
        from . import modelbus as _modelbus

        try:
            bus = _modelbus.ModelBus(self.dir, keep=0)
            versions = bus.versions()
            latest = bus.latest()
            quarantined = bus.quarantined()
        except Exception as e:  # never let bus trouble stall the loop
            obs["roles"][self.name] = {"kind": "model-bus",
                                       "dir": self.dir,
                                       "error": repr(e)}
            return
        rec = {"kind": "model-bus", "dir": self.dir,
               "versions": len(versions),
               "latest": latest.get("version") if latest else None,
               "model": latest.get("model") if latest else None,
               "step": latest.get("step") if latest else None,
               "quarantined": sorted(quarantined)}
        want = self.cfg.get("model")
        if want and latest and latest.get("model") \
                and latest.get("model") != want:
            rec["lineage_mismatch"] = (f"bus serves {latest['model']!r}, "
                                       f"spec expects {want!r}")
        obs["roles"][self.name] = rec

    def reconcile(self, obs):
        role_obs = obs["roles"][self.name]
        keep = int(self.cfg.get("keep") or 0)
        if keep > 0 and (role_obs.get("versions") or 0) > keep:
            return [{"kind": "bus-gc", "role": self.name,
                     "reason": f"{role_obs['versions']} versions > "
                               f"keep {keep}"}]
        return []

    def perform(self, action):
        if action["kind"] != "bus-gc":
            return
        from . import modelbus as _modelbus

        keep = int(self.cfg.get("keep") or 0)
        try:
            bus = _modelbus.ModelBus(self.dir, keep=0)
            mans = bus.manifests()
        except Exception as e:
            _logger.warning("cluster: bus gc skipped: %r", e)
            return
        if len(mans) <= keep:
            return
        kept = {m["version"] for m in mans[-keep:]}
        # a kept delta record's base must survive the sweep
        protect = {int(m["base_version"]) for m in mans[-keep:]
                   if m.get("base_version") is not None}
        dropped = [m["version"] for m in mans[:-keep]
                   if m["version"] not in protect
                   and m["version"] not in kept]
        for v in dropped:
            for path in (bus.payload_path(v), bus.manifest_path(v)):
                try:
                    os.unlink(path)
                except OSError:
                    pass
        if dropped:
            self.sup.world.record_action(
                "bus-gc", self.name,
                reason=f"dropped {len(dropped)} version(s), kept "
                       f"{len(kept)} (+{len(protect - kept)} bases)")

    def describe(self):
        return {"kind": "model-bus", "dir": self.dir,
                "keep": self.cfg.get("keep"),
                "model": self.cfg.get("model"), "state": self.state}


# --------------------------------------------------------- the supervisor --

_LIVE = weakref.WeakSet()
_collector_installed = False


def live_supervisors():
    """ClusterSupervisor instances alive in this process (diagnose)."""
    return list(_LIVE)


class ClusterSupervisor:
    """ONE reconciling loop over every role in a ``cluster.json`` spec.

    ``run()`` installs signal handlers (first SIGTERM/SIGINT drains the
    cluster, a second kills it), then ticks ``observe -> diff -> act``
    until every process role is terminal or a signal lands; the world
    record is re-published after every tick. Construction with a run
    dir that already holds ``world.json`` re-adopts the previous
    incarnation's workers (see module docstring for the rules).
    """

    def __init__(self, spec, run_dir=None, *, poll=0.25, env=None,
                 cwd=None, popen=None):
        import tempfile

        if isinstance(spec, (str, os.PathLike)):
            self.spec = load_spec(spec)
            self.spec_path = os.fspath(spec)
        else:
            self.spec = validate_spec(spec)
            self.spec_path = None
        self.run_dir = os.fspath(
            run_dir or os.environ.get("MXTPU_CLUSTER_DIR")
            or tempfile.mkdtemp(prefix="mxtpu_cluster_"))
        os.makedirs(self.run_dir, exist_ok=True)
        self.poll = float(poll)
        self.extra_env = dict(env or {})
        self.cwd = cwd
        self.popen = popen
        self._stop = threading.Event()
        self._signals = 0
        self._rc = 0
        self.ticks = 0
        self.adopted = 0

        # publish the spec next to the world record (diagnose reads it)
        spec_copy = os.path.join(self.run_dir, SPEC_FILE)
        if os.path.abspath(spec_copy) != os.path.abspath(
                self.spec_path or ""):
            atomic_record(spec_copy, self.spec)

        self.world = WorldState.load(self.run_dir)
        prev = self.world.supervisor or {}
        self.world.cluster = self.spec["cluster"]
        self.world.incarnation += 1
        self.world.supervisor = {
            "pid": os.getpid(),
            "start_ticks": proc_start_ticks(os.getpid()),
            "started": time.time(), "state": "reconciling",
            "previous": {k: prev.get(k) for k in ("pid", "started")}
            if prev else None}

        self.roles = {}
        for name, cfg in self.spec["roles"].items():
            cls = {"trainer-gang": _GangRole,
                   "serving-fleet": _ServeRole,
                   "model-bus": _BusRole}[cfg["kind"]]
            self.roles[name] = cls(self, name, cfg)
        self._readopt()
        for role in self.roles.values():
            if isinstance(role, _ServeRole):
                role.start_router()
        os.environ["MXTPU_CLUSTER_DIR"] = self.run_dir
        for role in self.roles.values():
            role.publish()
        self.world.save()
        _install_collector()
        _LIVE.add(self)
        _flight.rec("cluster.up", self.spec["cluster"],
                    f"incarnation {self.world.incarnation}")

    # ------------------------------------------------------------ helpers --
    def bus_dir(self, role_name):
        role = self.roles.get(role_name)
        if role is None or role.cfg["kind"] != "model-bus":
            raise ClusterError(f"{role_name!r} is not a model-bus role")
        return role.dir

    def _readopt(self):
        """Re-adopt the previous incarnation's slots from the world
        record (or classify their outage exits). A torn world record
        has no slot table to adopt from — fall back to observation-led
        adoption: rebuild the census from the workers' own heartbeat /
        announce shards so live processes are re-adopted instead of
        orphaned and then duplicated by fresh spawns."""
        for name, role in self.roles.items():
            if role.cfg["kind"] == "model-bus":
                continue
            recs = dict(self.world.slots.get(name) or {})
            if self.world.torn and not recs:
                scav = role.scavenge()
                recs = {str(k): v for k, v in scav.items()}
                if scav:
                    self.world.record_action(
                        "scavenge", name, None,
                        f"torn world record; {len(scav)} slot(s) "
                        "rebuilt from heartbeat/announce evidence")
            for rec in recs.values():
                verdict = role.adopt_from(rec)
                if verdict == "adopt":
                    self.adopted += 1
            if role.slots:
                # generation + next-slot survive a torn world too: they
                # must clear every adopted slot or respawns would reuse
                # live slot ids (announce-file collisions)
                role.generation = max(
                    [role.generation]
                    + [s.generation for s in role.slots.values()])
                role.next_slot = max(role.next_slot,
                                     max(role.slots) + 1)
                role.state = "running"
                role.note_adopted()

    # -------------------------------------------------------------- ticks --
    def _observe(self):
        obs = {"t": time.time(), "roles": {}}
        _faults.point("cluster.observe")
        for role in self.roles.values():
            role.observe(obs)
        return obs

    def _act(self, action):
        _faults.point("supervisor.act", action)
        _faults.point("cluster.act", action)
        self.roles[action["role"]].perform(action)

    def tick(self):
        """One reconcile pass: observe -> diff -> act -> publish. Both
        blocking halves run under watchdog spans (``cluster.observe`` /
        ``cluster.act``): a wedged pass hits the ladder like any other
        stalled sync point."""
        obs = _watchdog.sync("cluster.observe", self._observe,
                             label=self.spec["cluster"])
        actions = []
        for role in self.roles.values():
            role.escalate_drains()
            actions.extend(role.reconcile(obs))
        for action in actions:
            _watchdog.sync(
                "cluster.act", lambda a=action: self._act(a),
                label=f"{action['kind']} {action.get('role')}")
        self.ticks += 1
        for role in self.roles.values():
            role.publish()
        self.world.supervisor["state"] = "reconciling"
        self.world.save()
        if actions:
            _flight.rec("cluster.tick", self.spec["cluster"],
                        f"{len(actions)} action(s)")
        return obs, actions

    # ---------------------------------------------------------- lifecycle --
    def wait_ready(self, timeout=60.0):
        """Block until every process role has its desired census alive
        (serving roles: routable). Raises ClusterError on timeout."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            obs, _ = self.tick()
            ok = True
            for name, role in self.roles.items():
                if isinstance(role, _GangRole):
                    ok &= role.alive_count() >= int(role.cfg["workers"])
                elif isinstance(role, _ServeRole):
                    ok &= len(role._routable) >= role.desired
            if ok:
                return True
            time.sleep(min(self.poll, 0.1))
        raise ClusterError(
            f"cluster not ready within {timeout:g}s: "
            f"{ {n: r.describe().get('state') for n, r in self.roles.items()} }")

    def run(self):
        """Supervise until every process role is terminal (done/failed)
        or a signal lands. Returns the most severe role exit code (0
        for a clean drain)."""
        prev = {}
        try:
            for s in (_signal.SIGTERM, _signal.SIGINT):
                prev[s] = _signal.signal(s, self._on_signal)
        except ValueError:
            prev = {}
        try:
            while not self._stop.is_set():
                self.tick()
                process_roles = [r for r in self.roles.values()
                                 if not isinstance(r, _BusRole)]
                if process_roles and all(r.state in ("done", "failed")
                                         for r in process_roles):
                    break
                self._stop.wait(self.poll)
            self.stop(graceful=self._signals < 2)
        finally:
            for s, h in prev.items():
                try:
                    _signal.signal(s, h)
                except (ValueError, TypeError):
                    pass
        for role in self.roles.values():
            if role.state == "failed":
                exits = [s.last_exit for s in role.slots.values()
                         if s.last_exit is not None]
                self._rc = _preempt.most_severe([self._rc] + exits) or 1
        return self._rc

    def _on_signal(self, signum, frame):
        self._signals += 1
        self._stop.set()

    def stop(self, graceful=True):
        """Drain (or kill) every role, wait out the grace windows, and
        publish the final world record."""
        _flight.rec("cluster.stop", self.spec["cluster"],
                    "drain" if graceful else "kill")
        for role in self.roles.values():
            if not isinstance(role, _BusRole):
                role.stop(graceful=graceful)
        deadline = time.monotonic() + max(
            [float(r.cfg.get("grace", 10.0)) for r in
             self.roles.values()] + [1.0]) + 5.0
        while time.monotonic() < deadline:
            for role in self.roles.values():
                role.escalate_drains()
            if all(not s.alive() for r in self.roles.values()
                   for s in r.slots.values()):
                break
            time.sleep(0.05)
        for role in self.roles.values():
            for s in role.slots.values():
                if s.alive():
                    s.signal(_signal.SIGKILL)
                if s.state in ("running", "starting", "draining"):
                    code = s.exit_code(role.evidence_for(s.slot))
                    s.last_exit = code
                    s.state = "retired" if code in (
                        0, _preempt.DRAIN_EXIT_CODE) else "exited"
            if isinstance(role, _ServeRole):
                role.close_router()
            if role.state == "running":
                role.state = "done"
            role.publish()
        self.world.supervisor["state"] = "stopped"
        self.world.save()

    def describe(self):
        return {"cluster": self.spec["cluster"],
                "run_dir": self.run_dir,
                "incarnation": self.world.incarnation,
                "ticks": self.ticks, "adopted": self.adopted,
                "roles": {n: r.describe()
                          for n, r in self.roles.items()}}


# --------------------------------------------------- telemetry collector ---

def _collect_cluster():
    """Scrape-time ``mxtpu_cluster_*`` gauges for the most recent live
    supervisor in this process."""
    from .telemetry import registry as _registry

    sups = sorted(_LIVE, key=lambda s: s.world.supervisor.get(
        "started", 0))
    if not sups:
        return
    sup = sups[-1]
    _registry.gauge("mxtpu_cluster_incarnation",
                    "Supervisor incarnation (bumps per restart)"
                    ).set(sup.world.incarnation)
    _registry.counter("mxtpu_cluster_reconcile_ticks_total",
                      "Reconcile passes").set_total(sup.ticks)
    _registry.counter("mxtpu_cluster_adopted_total",
                      "Workers re-adopted across supervisor restarts"
                      ).set_total(sup.adopted)
    gen = _registry.gauge("mxtpu_cluster_generation",
                          "Role generation", labels=("role",))
    desired = _registry.gauge("mxtpu_cluster_slots_desired",
                              "Desired census per role",
                              labels=("role",))
    alive = _registry.gauge("mxtpu_cluster_slots_alive",
                            "Live slots per role", labels=("role",))
    restarts = _registry.counter("mxtpu_cluster_restarts_total",
                                 "Restarts charged per role",
                                 labels=("role",))
    for name, role in sup.roles.items():
        if isinstance(role, _BusRole):
            continue
        gen.set(role.generation, name)
        want = role.desired if isinstance(role, _ServeRole) \
            else int(role.cfg["workers"])
        desired.set(want, name)
        alive.set(role.alive_count(), name)
        restarts.set_total(role.ledger.restarts_total, name)


def _install_collector():
    global _collector_installed
    if _collector_installed:
        return
    _collector_installed = True
    from .telemetry import export as _export

    _export.register_collector("cluster", _collect_cluster)


def describe():
    """Module knobs + live state (tools/diagnose.py 'Cluster')."""
    return {"run_dir": os.environ.get("MXTPU_CLUSTER_DIR", "<unset>"),
            "live": [s.describe() for s in live_supervisors()]}
