"""ServingFleet: N ModelServer workers behind one router front door.

The ps-lite scheduler/server split (SURVEY §L7) replayed for inference:
one `ModelServer` process sustains thousands of req/s (PR 8), "millions
of users" needs N of them behind one address. Everything here composes
pieces the stack already has:

* **process plane** — a serving-mode supervisor
  (:class:`mxnet_tpu.elastic.ServingSupervisor`): per-slot restart with
  backoff, heartbeat liveness kills, the exit-code ladder; exit 75 on a
  deliberately drained slot retires it (rollout / scale-down) instead of
  restarting;
* **router** — an HTTP front end dispatching ``POST
  /v1/models/<m>:predict`` to workers over persistent (keep-alive)
  upstream connections. Placement: **least-loaded** (live queue depth
  from each worker's telemetry shard, falling back to round-robin when
  shards are missing/stale), **consistent-hash-by-model** (a vnode hash
  ring — a worker-set change only remaps the keys the lost worker
  owned), or plain round-robin. A connection-refused/reset upstream (a
  dying worker) is retried on a different worker — a request is only
  ever lost if NO worker can take it — and a worker's 503
  (draining/not-admitted) fails over the same way. Upstream timeouts are
  NOT retried: the batch may already be running;
* **autoscaler** — a control loop over the gauges telemetry already
  exports per worker (queue depth / p99 / batch fill / completion rate):
  sustained pressure for K samples scales up, sustained idle scales
  down, min/max bounds and a cooldown damp oscillation
  (``MXNET_TPU_FLEET`` grammar below);
* **zero-downtime rollout** — :meth:`ServingFleet.rollout` starts a
  generation-N+1 worker set from ``new_model_dir`` (warming from the
  persistent compile cache: a warm generation LOADS, never compiles),
  health-gates every new worker (``/healthz`` + an announce census
  showing ZERO pending compiles), shifts router traffic atomically,
  then drains generation N through the exit-75 protocol — mid-load,
  with zero dropped admitted requests.

``MXNET_TPU_FLEET`` env grammar (mirrors FAULTS/WATCHDOG: one variable,
``,``/``;``-separated ``option:value`` entries; constructor kwargs and
``config=`` override)::

    min:<N>            autoscaler lower bound (default 1)
    max:<N>            autoscaler upper bound (default 4; min==max
                       disables autoscaling)
    up_queue:<N>       scale-up pressure: any worker's queue depth >= N
                       (default 32)
    up_p99_ms:<F>      scale-up pressure: any worker's p99 >= F (250)
    up_fill:<F>        scale-up pressure: batch fill ratio >= F (0.98 —
                       full buckets mean the batcher is saturated)
    k:<N>              consecutive pressure samples before scaling up (3)
    idle_rps:<F>       scale-down: fleet completion rate <= F req/s with
                       empty queues (default 1.0)
    idle_k:<N>         consecutive idle samples before scaling down (5)
    cooldown:<F>       seconds after any scale action before the next (10)
    interval:<F>       autoscaler sampling period, seconds (1.0)
    policy:<P>         least_loaded | hash | round_robin (least_loaded)
    beat:<F>           worker heartbeat/telemetry-shard cadence (0.5)
    ready_timeout:<F>  worker-ready / rollout health-gate deadline (120)
    drain_timeout:<F>  generation drain deadline during rollout (60)
    grace:<F>          drain SIGTERM->SIGKILL escalation deadline (15)
    dead_after:<F>     heartbeat-silence kill threshold (30; 0 off)
    restarts:<N>       per-slot restart budget (5)
    timeout_ms:<F>     router upstream request deadline (30000)
    hedge:<0|1>        hedged requests: re-issue a straggling in-flight
                       request to a second worker after the hedge
                       threshold, first answer wins (default 1)
    hedge_factor:<F>   hedge threshold = router p99 x this factor (2.0)
    hedge_min_ms:<F>   hedge threshold floor — also the threshold used
                       against a flagged persistent-straggler worker (20)
    slo_ms:<F>         target p99 SLO: when set (> 0) the autoscaler
                       scales on p99-vs-SLO headroom (pressure at p99 >=
                       80% of the SLO) instead of raw queue depth /
                       fill; 0 keeps the queue-depth policy (default 0)

Multi-host: pass ``hosts=[...]`` to place workers across machines — each
entry is a name (``"local"``), an ssh destination (``"user@h2"``), or a
dict ``{name, ssh, cwd, env, advertise, locality}``. Remote workers are
launched through the same ssh path the gang supervisor uses
(:func:`mxnet_tpu.elastic._ssh_argv`); every host gets its own run
(sub)dir — heartbeats and telemetry shards are merged at scrape — and
the router becomes locality-aware: local workers are preferred, remote
ones take the spill with a measured latency penalty. The 2-host chaos
drill runs two "hosts" on localhost with distinct run dirs; a genuinely
remote host needs this repo importable at the same path (shared
filesystem or an rsynced checkout) and the run dir on shared storage.

Hedging semantics (docs/SERVING.md "Planet scale"): only the FIRST
attempt hedges, and only when the primary is merely *slow* — a primary
that fails fast takes the ordinary failover path, and a primary that
hits the upstream timeout without a hedge already in flight is NEVER
hedged after the fact (the batch may be running; "zero dropped admitted
requests" forbids re-issuing). First answer wins; the loser's connection
is closed (the worker still answers its donating batch — content-keyed
in-flight dedupe on the worker makes the duplicate free when both copies
land on one worker).

Quick start::

    from mxnet_tpu.serving import fleet, worker

    worker.write_spec(model_dir, worker.demo_spec(models=2))
    f = fleet.ServingFleet(model_dir, workers=2).start()
    ...                           # drive f.url like any serving front end
    f.rollout(new_model_dir)      # zero-downtime model swap
    f.stop()

Observability: ``fleet.json`` in the run dir (census, autoscaler state,
rollout history, router counters — the diagnose "Serving Fleet" report),
``mxtpu_fleet_*`` gauges on the router's ``/metrics`` (generation,
ready/desired workers, fleet rps, router/autoscale counters, plus the
per-rank re-exports from :mod:`mxnet_tpu.telemetry.fleet`), and
``fleet.*`` flight events for every lifecycle transition.
"""
from __future__ import annotations

import collections
import hashlib
import http.client
import json
import os
import re
import socket
import sys
import threading
import time
import weakref

from .. import log as _log
from ..telemetry import flight as _flight
from . import worker as _worker
from .errors import ServingError

__all__ = ["ServingFleet", "FleetError", "Autoscaler", "HashRing",
           "order_candidates", "gate_ready", "worker_metrics",
           "hedged_call", "normalize_hosts", "HedgeGovernor",
           "configure", "effective",
           "describe", "live_fleets", "DEFAULTS", "ENV", "POLICIES"]

_logger = _log.get_logger("mxnet_tpu.serving.fleet")

ENV = "MXNET_TPU_FLEET"

POLICIES = ("least_loaded", "hash", "round_robin")

DEFAULTS = {
    "min": 1,
    "max": 4,
    "up_queue": 32,
    "up_p99_ms": 250.0,
    "up_fill": 0.98,
    "k": 3,
    "idle_rps": 1.0,
    "idle_k": 5,
    "cooldown": 10.0,
    "interval": 1.0,
    "policy": "least_loaded",
    "beat": 0.5,
    "ready_timeout": 120.0,
    "drain_timeout": 60.0,
    "grace": 15.0,
    "dead_after": 30.0,
    "restarts": 5,
    "timeout_ms": 30000.0,
    "hedge": 1,
    "hedge_factor": 2.0,
    "hedge_min_ms": 20.0,
    "slo_ms": 0.0,
}

_INT_KEYS = ("min", "max", "up_queue", "k", "idle_k", "restarts", "hedge")
_FLOAT_KEYS = ("up_p99_ms", "up_fill", "idle_rps", "cooldown", "interval",
               "beat", "ready_timeout", "drain_timeout", "grace",
               "dead_after", "timeout_ms", "hedge_factor", "hedge_min_ms",
               "slo_ms")

_cfg_lock = threading.Lock()
_CFG: dict | None = None
_loaded_env = False


class FleetError(ServingError):
    """Fleet-level failure: workers never became ready, a rollout's
    health gate timed out, or the fleet was asked to serve with no
    routable workers."""


def _coerce(key, val):
    if key == "policy":
        v = str(val).strip().lower()
        if v not in POLICIES:
            raise ValueError(f"unknown fleet policy {val!r}; expected one "
                             f"of {POLICIES}")
        return v
    if key in _INT_KEYS:
        n = int(val)
        if n < 0 or (n < 1 and key in ("min", "max")):
            raise ValueError(f"fleet {key} must be >= 1, got {n}")
        return n
    if key in _FLOAT_KEYS:
        f = float(val)
        if f < 0:
            raise ValueError(f"fleet {key} must be >= 0, got {f}")
        return f
    raise ValueError(f"unknown fleet option {key!r}; expected one of "
                     f"{sorted(DEFAULTS)}")


def _parse(spec):
    cfg = dict(DEFAULTS)
    for entry in re.split(r"[;,]", spec):
        entry = entry.strip()
        if not entry:
            continue
        key, sep, val = entry.partition(":")
        key, val = key.strip(), val.strip()
        if not sep or not val:
            raise ValueError(
                f"bad {ENV} entry {entry!r}: expected <option>:<value>")
        cfg[key] = _coerce(key, val)
    if cfg["max"] < cfg["min"]:
        raise ValueError(f"fleet max ({cfg['max']}) < min ({cfg['min']})")
    return cfg


def configure(spec=None, **options):
    """Install a fleet configuration (grammar string, dict, or kwargs on
    top of the defaults); pass nothing to reset to env/defaults."""
    global _CFG, _loaded_env
    if isinstance(spec, dict):
        cfg = dict(DEFAULTS)
        for k, v in spec.items():
            cfg[k] = _coerce(k, v)
    elif spec:
        cfg = _parse(spec)
    else:
        cfg = dict(DEFAULTS)
    for k, v in options.items():
        cfg[k] = _coerce(k, v)
    if cfg["max"] < cfg["min"]:
        raise ValueError(f"fleet max ({cfg['max']}) < min ({cfg['min']})")
    with _cfg_lock:
        _loaded_env = True
        _CFG = cfg
    return dict(cfg)


def _ensure_env():
    global _loaded_env, _CFG
    if _loaded_env:
        return
    with _cfg_lock:
        if _loaded_env:
            return
        _loaded_env = True
        env = os.environ.get(ENV, "")
        if env:
            try:
                _CFG = _parse(env)
            except ValueError as e:
                _logger.warning("ignoring invalid %s: %s", ENV, e)
                _CFG = None


def effective() -> dict:
    """The effective fleet configuration (env-seeded, configure-wins)."""
    _ensure_env()
    cfg = _CFG
    return dict(cfg) if cfg is not None else dict(DEFAULTS)


def describe() -> dict:
    """Knobs + provenance (tools/diagnose.py 'Serving Fleet')."""
    out = effective()
    out["env"] = os.environ.get(ENV, "<unset>")
    return out


def _env_float(name, default):
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return float(default)


# ------------------------------------------------------- routing policies --

def _hash32(s):
    return int(hashlib.md5(str(s).encode()).hexdigest()[:8], 16)


class HashRing:
    """Consistent hashing over worker slots (``vnodes`` points per slot):
    removing a worker only remaps the keys that worker owned; the other
    keys keep their placement — the property the fleet's
    consistent-hash-by-model policy needs across worker churn."""

    def __init__(self, slots=(), vnodes=64):
        self.vnodes = int(vnodes)
        self._ring = []            # sorted [(point, slot)]
        self.rebuild(slots)

    def rebuild(self, slots):
        self._ring = sorted(
            (_hash32(f"{slot}:{v}"), slot)
            for slot in set(slots) for v in range(self.vnodes))
        return self

    def lookup(self, key, allowed=None):
        """The slot owning `key` (restricted to `allowed` when given);
        None on an empty ring."""
        ring = self._ring
        if not ring:
            return None
        h = _hash32(key)
        lo, hi = 0, len(ring)
        while lo < hi:
            mid = (lo + hi) // 2
            if ring[mid][0] < h:
                lo = mid + 1
            else:
                hi = mid
        for i in range(len(ring)):
            slot = ring[(lo + i) % len(ring)][1]
            if allowed is None or slot in allowed:
                return slot
        return None


def order_candidates(policy, model, slots, depths=None, rr=0, ring=None,
                     localities=None, remote_penalty=0.0):
    """Order the routable `slots` for one request: the head is the
    placement choice, the tail is the failover order.

    * ``least_loaded`` — ascending live queue depth (unknown depth
      counts as 0: a fresh worker has an empty queue), round-robin
      rotation breaking ties; with NO depth known at all this degrades
      to pure round-robin.
    * ``hash`` — the consistent-hash owner of `model` first, the rest
      rotated.
    * ``round_robin`` — rotation by the request counter.

    Locality: with ``localities`` (``{slot: "local"|"remote"}``) the
    router prefers local/ICI workers and spills to remote/DCN ones with
    a MEASURED penalty — ``remote_penalty`` is the observed extra cost
    of a remote hop expressed in queue-rows equivalents (extra latency /
    local service time), so a remote worker only wins the placement when
    it is more than that many rows *less* loaded. Non-depth policies
    stable-partition local candidates first (the hash owner still wins
    its key: determinism beats locality for affinity routing).
    """
    slots = list(slots)
    if not slots:
        return []

    def _remote(s):
        return localities is not None and localities.get(s) == "remote"

    k = rr % len(slots)
    rotated = slots[k:] + slots[:k]
    if policy == "hash" and ring is not None:
        primary = ring.lookup(model, allowed=set(slots))
        rest = [s for s in rotated if s != primary]
        if localities:
            rest = [s for s in rest if not _remote(s)] + \
                [s for s in rest if _remote(s)]
        if primary is None:
            return rest
        return [primary] + rest
    if policy == "least_loaded" and depths \
            and any(depths.get(s) is not None for s in slots):
        return sorted(rotated, key=lambda s: (depths.get(s) or 0)
                      + (remote_penalty if _remote(s) else 0.0))
    if localities:
        return [s for s in rotated if not _remote(s)] + \
            [s for s in rotated if _remote(s)]
    return rotated


def gate_ready(announce):
    """The rollout health gate's announce half: a worker may take
    traffic only when it announced ``serving`` + ``ready`` with ZERO
    pending compiles (an unwarmed ladder would recompile under traffic —
    exactly what a rollout must never do)."""
    return (bool(announce)
            and announce.get("state") == "serving"
            and bool(announce.get("ready"))
            and int(announce.get("pending_compiles") or 0) == 0)


# ------------------------------------------------------------- hedging ----

def hedged_call(primary, hedge, hedge_after, timeout=None):
    """The hedged-request core, pure threading so it table-tests:
    run ``primary()`` on a worker thread; when it has not answered
    within ``hedge_after`` seconds, issue ``hedge()`` too — the first
    SUCCESSFUL answer wins and the loser is abandoned (the caller closes
    the loser's connection; its thread drains into the result record).

    The retry/timeout contract is preserved by construction:

    * a primary that FINISHES (success or error) before the threshold is
      returned as-is, un-hedged — fast failures take the ordinary
      failover path, hedging only covers the slow-but-alive case;
    * once the hedge is in flight, a primary error (including a timeout)
      legally waits for the already-issued hedge — nothing NEW is ever
      issued after a failure;
    * both failing reports the primary's error (so an upstream timeout
      still surfaces as the 504 the no-replay rule demands).

    Returns a record — never raises::

        {"winner": "primary"|"hedge"|None, "value": ..., "hedged": bool,
         "primary_error": exc|None, "hedge_error": exc|None}
    """
    cond = threading.Condition()
    state = {}

    def run(which, fn):
        try:
            out = (True, fn())
        except BaseException as e:     # noqa: BLE001 — recorded, not lost
            out = (False, e)
        with cond:
            state[which] = out
            cond.notify_all()

    def rec(winner=None, value=None, hedged=False):
        prim, hed = state.get("primary"), state.get("hedge")
        return {"winner": winner, "value": value, "hedged": hedged,
                "primary_error": prim[1] if prim and not prim[0] else None,
                "hedge_error": hed[1] if hed and not hed[0] else None}

    threading.Thread(target=run, args=("primary", primary),
                     daemon=True, name="mxtpu-hedge-primary").start()
    with cond:
        cond.wait_for(lambda: "primary" in state, timeout=hedge_after)
        prim = state.get("primary")
    if prim is not None:
        # answered (or failed) before the threshold: no hedge issued
        if prim[0]:
            return rec(winner="primary", value=prim[1])
        return rec()
    threading.Thread(target=run, args=("hedge", hedge),
                     daemon=True, name="mxtpu-hedge-secondary").start()
    deadline = None if timeout is None else time.monotonic() + timeout
    with cond:
        while True:
            prim, hed = state.get("primary"), state.get("hedge")
            if prim is not None and prim[0]:
                return rec(winner="primary", value=prim[1], hedged=True)
            if hed is not None and hed[0]:
                return rec(winner="hedge", value=hed[1], hedged=True)
            if prim is not None and hed is not None:
                return rec(hedged=True)    # both failed: primary's error
            left = None if deadline is None else deadline - time.monotonic()
            if left is not None and left <= 0:
                return rec(hedged=True)    # caller's backstop expired
            cond.wait(timeout=0.25 if left is None else min(left, 0.25))


# ------------------------------------------------------------ multi-host --

def normalize_hosts(hosts):
    """Canonicalise the ``hosts=`` argument into placement records::

        {name, ssh (None = spawn locally), cwd, env, advertise,
         locality ("local" | "remote")}

    Accepted entries: a plain name (``"local"`` / ``"localhost"`` spawn
    locally; anything else is an ssh destination), or a dict with any of
    the keys above. ``advertise`` is the address the worker binds (and
    announces) its HTTP port on — remote hosts default to their ssh host
    part so the router can reach them; local ones stay on loopback."""
    out = []
    seen = set()
    for i, spec in enumerate(hosts or ()):
        if isinstance(spec, str):
            if spec.strip().lower() in ("local", "localhost", "127.0.0.1"):
                spec = {"name": spec.strip().lower()}
            else:
                spec = {"ssh": spec.strip()}
        elif not isinstance(spec, dict):
            raise ValueError(f"bad fleet host spec {spec!r}: expected a "
                             "name/ssh string or a dict")
        else:
            spec = dict(spec)
        bad = set(spec) - {"name", "ssh", "cwd", "env", "advertise",
                           "locality"}
        if bad:
            raise ValueError(f"bad fleet host spec keys {sorted(bad)}; "
                             "expected name/ssh/cwd/env/advertise/locality")
        ssh = spec.get("ssh")
        name = spec.get("name") or \
            (re.sub(r"[^A-Za-z0-9_.-]", "_", ssh) if ssh else f"host{i}")
        if name in seen:
            raise ValueError(f"duplicate fleet host name {name!r}")
        seen.add(name)
        locality = spec.get("locality") or ("remote" if ssh else "local")
        if locality not in ("local", "remote"):
            raise ValueError(f"bad fleet host locality {locality!r}: "
                             "expected 'local' or 'remote'")
        advertise = spec.get("advertise") or \
            ((ssh.rsplit("@", 1)[-1] if ssh else "127.0.0.1"))
        out.append({"name": str(name), "ssh": ssh,
                    "cwd": spec.get("cwd"),
                    "env": dict(spec.get("env") or {}),
                    "advertise": advertise, "locality": locality})
    return out


class _HostPlane:
    """N per-host :class:`~mxnet_tpu.elastic.ServingSupervisor`\\ s
    behind the single-supervisor surface the fleet drives: every call
    routes by the fleet's slot->host assignment, census/slots/events
    merge (slot ids are globally unique, so a union is exact)."""

    def __init__(self, sups, slot_host):
        self._sups = sups          # {host name: ServingSupervisor}
        self._slot_host = slot_host  # the fleet's live slot->host map

    def _for(self, slot):
        return self._sups[self._slot_host[slot]]

    def spawn(self, slot, generation):
        return self._for(slot).spawn(slot, generation)

    def drain_slot(self, slot, reason=""):
        return self._for(slot).drain_slot(slot, reason=reason)

    def kill_slot(self, slot):
        return self._for(slot).kill_slot(slot)

    def poll(self):
        out = {}
        for sup in self._sups.values():
            out.update(sup.poll())
        return out

    def census(self):
        out = {}
        for sup in self._sups.values():
            out.update(sup.census())
        return out

    def stop_all(self, graceful=True, timeout=None):
        for sup in self._sups.values():
            sup.stop_all(graceful=graceful, timeout=timeout)

    @property
    def slots(self):
        out = {}
        for sup in self._sups.values():
            out.update(sup.slots)
        return out

    @property
    def events(self):
        out = []
        for sup in self._sups.values():
            out.extend(sup.events)
        return sorted(out, key=lambda ev: ev.get("t_wall", 0.0))

    @property
    def restarts_total(self):
        return sum(s.restarts_total for s in self._sups.values())

    @property
    def drained_total(self):
        return sum(s.drained_total for s in self._sups.values())


class HedgeGovernor:
    """Router-side latency book-keeping + hedge planning, shared by
    :class:`ServingFleet` and the cluster reconciler's serving-fleet
    role (both drive the same ``_RouterFront``): the p99 ring feeding
    the hedge threshold, per-slot EWMAs feeding persistent-straggler
    flags (same env knobs as the gang detector —
    ``MXNET_TPU_STRAGGLER_FACTOR`` / ``_PERSIST``), per-locality EWMAs
    feeding the remote spill penalty, and the fired/won/lost/failed
    counters. Pure state + arithmetic, so it table-tests."""

    def __init__(self, cfg, locality_of=None):
        self.cfg = cfg
        self._locality_of = locality_of or (lambda slot: "local")
        self._lock = threading.Lock()
        self.ring = collections.deque(maxlen=512)
        self._slot_ewma = {}       # slot -> (ewma_ms, samples)
        self._loc_ewma = {}        # locality -> ewma_ms
        self._streak = {}
        self.stragglers = frozenset()
        self.counters = {"fired": 0, "won": 0, "lost": 0, "failed": 0}

    def note(self, slot, ms):
        """One completed router request against `slot` took `ms`
        end-to-end."""
        ms = float(ms)
        loc = self._locality_of(slot)
        with self._lock:
            self.ring.append(ms)
            e, n = self._slot_ewma.get(slot, (None, 0))
            self._slot_ewma[slot] = (
                ms if e is None else 0.8 * e + 0.2 * ms, n + 1)
            le = self._loc_ewma.get(loc)
            self._loc_ewma[loc] = ms if le is None \
                else 0.8 * le + 0.2 * ms

    def count(self, outcome):
        with self._lock:
            self.counters[outcome] = self.counters.get(outcome, 0) + 1

    def remote_penalty(self):
        """The measured extra cost of a remote hop, in queue-rows
        equivalents: (remote EWMA - local EWMA) / local EWMA. Zero until
        both localities have answered requests."""
        with self._lock:
            local = self._loc_ewma.get("local")
            remote = self._loc_ewma.get("remote")
        if not local or not remote:
            return 0.0
        return max(0.0, (remote - local) / max(local, 1e-3))

    def threshold(self, slot):
        """Milliseconds to wait before hedging a first attempt against
        `slot`, or None (not enough signal yet). A flagged persistent
        straggler gets the ``hedge_min_ms`` floor immediately; otherwise
        the router's own p99 x ``hedge_factor``, floored at
        ``hedge_min_ms`` and capped at half the upstream timeout (a
        hedge that can't finish inside the remaining budget is
        pointless)."""
        if slot in self.stragglers:
            return self.cfg["hedge_min_ms"]
        with self._lock:
            ring = sorted(self.ring)
        if len(ring) < 16:
            return None
        p99 = ring[int(0.99 * (len(ring) - 1))]
        thr = max(self.cfg["hedge_min_ms"],
                  p99 * self.cfg["hedge_factor"])
        return min(thr, self.cfg["timeout_ms"] / 2.0)

    # one request in PROBE_EVERY keeps its natural placement even when
    # that placement is a flagged straggler: the probe is hedged at the
    # hedge_min_ms floor (cheap rescue), and a RECOVERED slot wins its
    # own probe races, decaying its EWMA until the flag clears —
    # without probes a flagged slot could never prove itself healthy
    PROBE_EVERY = 16

    def reorder(self, order, rr):
        """Stable-move flagged persistent stragglers to the tail of the
        candidate `order` — they stay reachable (failover, hedges) but
        stop being anyone's first choice. Every ``PROBE_EVERY``-th
        request passes through unmoved as a canary probe."""
        flagged = self.stragglers
        if not flagged or rr % self.PROBE_EVERY == 0:
            return order
        return [s for s in order if s not in flagged] + \
            [s for s in order if s in flagged]

    def plan(self, slot, candidates, endpoint):
        """(hedge slot, threshold ms) for a first attempt against
        `slot`, or (None, None) when hedging is off / there is no second
        candidate with a live `endpoint` / the latency signal is too
        thin."""
        if not self.cfg.get("hedge") or len(candidates) < 2:
            return None, None
        thr = self.threshold(slot)
        if thr is None:
            return None, None
        for cand in candidates:
            if cand != slot and endpoint(cand) is not None:
                return cand, thr
        return None, None

    def update_stragglers(self, active):
        """Advance the per-slot flag streaks (call once per control
        interval): a slot whose latency EWMA stayed >= factor x the
        fleet median for `persist` consecutive calls is flagged."""
        factor = _env_float("MXNET_TPU_STRAGGLER_FACTOR", 1.5)
        persist = int(_env_float("MXNET_TPU_STRAGGLER_PERSIST", 3))
        active = set(active) | set(self.stragglers)
        with self._lock:
            ew = {s: e for s, (e, n) in self._slot_ewma.items()
                  if n >= 5 and s in active}
        if len(ew) < 2:
            self._streak = {}
            self.stragglers = frozenset()
            return self.stragglers
        # lower-middle median: with an even count (the 2-host fleet!)
        # the upper-middle would BE the straggler's own EWMA and the
        # flag could never fire
        vals = sorted(ew.values())
        median = vals[(len(vals) - 1) // 2]
        flagged_now = {s for s, e in ew.items()
                       if e >= factor * max(median, 1e-9)}
        self._streak = {s: self._streak.get(s, 0) + 1
                        for s in flagged_now}
        new = frozenset(s for s, n in self._streak.items()
                        if n >= persist)
        for s in sorted(new - self.stragglers):
            _flight.rec("fleet.straggler", f"slot{s}",
                        f"ewma {ew[s]:.1f}ms >= {factor:g}x median "
                        f"{median:.1f}ms")
        self.stragglers = new
        return self.stragglers

    def describe(self):
        """{hedges, stragglers, router_latency} for stats()/diagnose."""
        with self._lock:
            counters = dict(self.counters)
            ring = sorted(self.ring)
            by_loc = {k: round(v, 3) for k, v in self._loc_ewma.items()}
        lat = None
        if ring:
            lat = {"samples": len(ring),
                   "p50_ms": round(ring[len(ring) // 2], 3),
                   "p99_ms": round(ring[int(0.99 * (len(ring) - 1))], 3),
                   "by_locality_ewma_ms": by_loc}
        return {"hedges": counters,
                "stragglers": sorted(self.stragglers),
                "router_latency": lat}


# ---------------------------------------------------------- shard reading --

def _series_values(shard, name, **match):
    out = []
    metric = (shard.get("metrics") or {}).get(name)
    if not isinstance(metric, dict):
        return out
    for series in metric.get("series") or ():
        labels = series.get("labels") or {}
        if all(labels.get(k) == v for k, v in match.items()):
            v = series.get("value")
            if isinstance(v, (int, float)):
                out.append(float(v))
    return out


def worker_metrics(run_dir, slots=None):
    """Per-worker serving gauges from the telemetry shards each worker
    co-writes with its heartbeat: ``{slot: {queue_depth, p99_ms, fill,
    completed, rps, age_s, generation}}``. Missing/torn shards are
    simply absent — callers fall back (router: round-robin; autoscaler:
    no pressure signal from that worker)."""
    from ..telemetry import fleet as _tfleet

    out = {}
    now = time.time()
    for rank, shard in _tfleet.read_shards(run_dir).items():
        if slots is not None and rank not in slots:
            continue
        depth = _series_values(shard, "mxtpu_serving_queue_depth")
        p99 = _series_values(shard, "mxtpu_serving_latency_ms",
                             quantile="p99")
        fill = _series_values(shard, "mxtpu_serving_batch_fill_ratio")
        done = _series_values(shard, "mxtpu_serving_requests_total",
                              outcome="completed")
        rps = _series_values(shard, "mxtpu_serving_rps")
        out[rank] = {
            "queue_depth": sum(depth) if depth else None,
            "p99_ms": max(p99) if p99 else None,
            "fill": max(fill) if fill else None,
            "completed": sum(done) if done else 0.0,
            "rps": sum(rps) if rps else None,
            "age_s": round(now - float(shard.get("t_wall", now)), 3),
            "generation": shard.get("generation"),
        }
    return out


# -------------------------------------------------------------- autoscaler --

class Autoscaler:
    """The scaling decision core, pure enough to table-test: feed it one
    aggregate sample per interval and it answers up/down/None.

    Pressure (any of: max queue depth >= ``up_queue``, max p99 >=
    ``up_p99_ms``, max batch fill >= ``up_fill``) sustained for ``k``
    consecutive samples scales up; idleness (completion rate <=
    ``idle_rps`` AND empty queues) sustained for ``idle_k`` samples
    scales down; every action starts a ``cooldown`` window during which
    streaks keep accumulating but nothing fires; ``min``/``max`` bound
    the census.

    SLO mode (``slo_ms`` > 0): pressure becomes p99-vs-SLO **headroom**
    instead of the raw queue/fill thresholds — the fleet scales up when
    p99 eats 80% of the SLO budget, i.e. *before* the SLO is breached,
    not after the queue is already deep (a deep queue means the p99 the
    clients saw was already lost). Idleness is unchanged: completion
    rate is the only trustworthy scale-down signal either way."""

    def __init__(self, cfg=None):
        self.cfg = dict(effective() if cfg is None else cfg)
        self.up_streak = 0
        self.idle_streak = 0
        self.cooldown_until = 0.0
        self.last = None           # last decision record (incl. holds)
        self.last_action = None    # last actual up/down
        self.decisions = {"up": 0, "down": 0}

    def decide(self, sample, workers, now=None):
        """One sample -> ("up"|"down"|None, record). `sample` carries
        ``queue_depth``/``p99_ms``/``fill`` (fleet-max) + ``rps``
        (fleet completion rate); `workers` is the current census."""
        cfg = self.cfg
        now = time.monotonic() if now is None else now
        pressure = []
        q = sample.get("queue_depth")
        p99 = sample.get("p99_ms")
        slo = cfg.get("slo_ms") or 0.0
        if slo > 0:
            # SLO mode: the only up-pressure is exhausted p99 headroom
            budget = 0.8 * slo
            if p99 is not None and p99 >= budget:
                pressure.append(
                    f"p99 {p99:g}ms >= 80% of {slo:g}ms SLO")
        else:
            if q is not None and q >= cfg["up_queue"]:
                pressure.append(f"queue {q:g} >= {cfg['up_queue']}")
            if p99 is not None and p99 >= cfg["up_p99_ms"]:
                pressure.append(f"p99 {p99:g}ms >= {cfg['up_p99_ms']:g}")
            fill = sample.get("fill")
            if fill is not None and fill >= cfg["up_fill"]:
                pressure.append(f"fill {fill:g} >= {cfg['up_fill']:g}")
        rps = sample.get("rps")
        # idleness takes PRECEDENCE over pressure: p99/fill are
        # recent-window gauges that stay high after traffic stops — an
        # empty-queue fleet completing nothing is idle no matter what
        # its stale latency gauges say
        idle = (rps is not None and rps <= cfg["idle_rps"] and not q)
        if idle:
            self.idle_streak += 1
            self.up_streak = 0
        elif pressure:
            self.up_streak += 1
            self.idle_streak = 0
        else:
            self.up_streak = 0
            self.idle_streak = 0
        direction, why = None, None
        if self.up_streak >= cfg["k"]:
            if workers >= cfg["max"]:
                why = f"at max ({cfg['max']})"
            elif now < self.cooldown_until:
                why = "cooling down"
            else:
                direction = "up"
                why = "; ".join(pressure)
        elif self.idle_streak >= cfg["idle_k"]:
            if workers <= cfg["min"]:
                why = f"at min ({cfg['min']})"
            elif now < self.cooldown_until:
                why = "cooling down"
            else:
                direction = "down"
                why = (f"idle: rps {rps:g} <= {cfg['idle_rps']:g} for "
                       f"{self.idle_streak} samples")
        rec = {"t_wall": time.time(), "direction": direction,
               "reason": why, "workers": workers,
               "up_streak": self.up_streak,
               "idle_streak": self.idle_streak,
               "sample": {k: sample.get(k) for k in
                          ("queue_depth", "p99_ms", "fill", "rps")}}
        self.last = rec
        if direction is not None:
            self.cooldown_until = now + cfg["cooldown"]
            self.up_streak = 0
            self.idle_streak = 0
            self.decisions[direction] += 1
            self.last_action = rec
        return direction, rec

    def describe(self):
        return {"last": self.last, "last_action": self.last_action,
                "decisions": dict(self.decisions),
                "up_streak": self.up_streak,
                "idle_streak": self.idle_streak,
                "enabled": self.cfg["max"] > self.cfg["min"]}


# ------------------------------------------------------------- the router --

_PREDICT_RE = re.compile(r"^/(?:v1/models|models|predict)/([^/:]+)"
                         r"(?::predict)?$")

#: upstream failures safe to retry on ANOTHER worker: the connection
#: died before (or instead of) a response — the request was never
#: admitted there. A timeout is NOT in this set: the batch may already
#: be running, and "zero dropped admitted requests" forbids guessing.
_RETRYABLE = (ConnectionError, http.client.HTTPException,
              socket.gaierror)


class _RouterFront:
    """The fleet's HTTP front door: proxies predict traffic to workers
    over persistent per-thread upstream connections, retrying
    connection-level failures (and worker 503s — not-admitted by
    construction) on the next candidate."""

    def __init__(self, fleet, host="127.0.0.1", port=0):
        from http.server import BaseHTTPRequestHandler, \
            ThreadingHTTPServer

        self._fleet = fleet
        self._local = threading.local()
        front = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            server_version = "mxtpu-fleet/0.1"
            # keep-alive + separate header/body sends otherwise hit the
            # Nagle x delayed-ACK 40ms stall — even on loopback
            disable_nagle_algorithm = True

            def log_message(self, *args):
                pass

            def _json(self, code, payload, extra_headers=()):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in extra_headers:
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                fl = front._fleet
                if self.path == "/healthz":
                    st = fl.stats(light=True)
                    ok = st["ready"] >= 1
                    self._json(200 if ok else 503,
                               {"status": "ok" if ok else "degraded",
                                "generation": st["generation"],
                                "workers_ready": st["ready"],
                                "workers_desired": st["desired"]})
                elif self.path in ("/v1/models", "/models"):
                    self._json(200, fl.models())
                elif self.path in ("/v1/stats", "/stats"):
                    self._json(200, fl.stats())
                elif self.path == "/metrics":
                    from ..telemetry import export as _export

                    body = _export.render_prometheus().encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     _export.PROMETHEUS_CONTENT_TYPE)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif self.path == "/metrics.json":
                    from ..telemetry import export as _export

                    body = _export.render_json().encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                else:
                    self._json(404, {"error": f"no route {self.path!r}"})

            def do_POST(self):
                m = _PREDICT_RE.match(self.path)
                if not m:
                    self._json(404, {"error": f"no route {self.path!r}"})
                    return
                length = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(length)
                rid = self.headers.get("X-Request-Id")
                if not rid:
                    from ..telemetry import trace as _trace

                    rid = _trace.new_request_id()
                status, payload, hdrs = front._dispatch(
                    m.group(1), self.path, body,
                    self.headers.get("Content-Type", "application/json"),
                    rid)
                self.send_response(status)
                for k, v in hdrs:
                    self.send_header(k, v)
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._httpd.daemon_threads = True
        self._thread = None

    # ------------------------------------------------------- dispatching --
    def _conn_to(self, slot, endpoint):
        conns = getattr(self._local, "conns", None)
        if conns is None:
            conns = self._local.conns = {}
        conn, ep = conns.get(slot, (None, None))
        if conn is None or ep != endpoint:
            if conn is not None:
                try:
                    conn.close()
                except OSError:
                    pass
            conn = http.client.HTTPConnection(
                endpoint[0], endpoint[1],
                timeout=self._fleet.cfg["timeout_ms"] / 1e3)
            conn.connect()
            # persistent upstream: TCP_NODELAY or every request eats the
            # Nagle x delayed-ACK stall
            conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY,
                                 1)
            conns[slot] = (conn, endpoint)
        return conn

    def _drop_conn(self, slot):
        conns = getattr(self._local, "conns", None)
        if conns:
            conn, _ = conns.pop(slot, (None, None))
            if conn is not None:
                try:
                    conn.close()
                except OSError:
                    pass

    def _fresh_conn(self, endpoint):
        """A one-shot upstream connection (hedges ride these so a
        cancelled loser never poisons the per-thread keep-alive pool)."""
        conn = http.client.HTTPConnection(
            endpoint[0], endpoint[1],
            timeout=self._fleet.cfg["timeout_ms"] / 1e3)
        conn.connect()
        conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return conn

    @staticmethod
    def _forward_on(conn, path, body, ctype, rid):
        """One upstream POST on an explicit connection. Returns
        ``(status, payload, content_type, retry_after)``; raises the
        connection-level failures the dispatch ladder classifies."""
        conn.request("POST", path, body=body,
                     headers={"Content-Type": ctype,
                              "X-Request-Id": rid})
        resp = conn.getresponse()
        payload = resp.read()
        return (resp.status, payload,
                resp.getheader("Content-Type", "application/json"),
                resp.getheader("Retry-After", "0.1"))

    def _dispatch(self, model, path, body, ctype, rid):
        """Route one admitted-at-the-front-door request: walk the
        policy-ordered candidates; connection-level failures and 503s
        fail over to the next worker; the LAST candidate's verdict (or a
        fleet 503) goes back to the client. The first attempt may be
        HEDGED: when the primary is slower than the hedge threshold
        (router p99 x hedge_factor, floored at hedge_min_ms, immediate
        floor for flagged stragglers) the same request is issued to the
        next candidate and the first answer wins."""
        fleet = self._fleet
        fleet._count("requests")
        rid_hdr = [("X-Request-Id", rid)]
        from .. import faults as _faults

        try:
            # 'serving.route' injection: delay = a slow route (drills
            # the hedge threshold), raise = a broken router hop
            _faults.point("serving.route")
        except Exception as e:
            fleet._count("errors")
            return 500, json.dumps(
                {"error": f"router fault: {type(e).__name__}: {e}",
                 "request_id": rid}).encode(), \
                rid_hdr + [("Content-Type", "application/json")]
        candidates = fleet.pick(model)
        if not candidates:
            fleet._count("rejects")
            return 503, json.dumps(
                {"error": "no ready workers in the fleet",
                 "request_id": rid}).encode(), \
                rid_hdr + [("Content-Type", "application/json"),
                           ("Retry-After", "1")]
        last_err = None
        t_req = time.monotonic()
        for attempt, slot in enumerate(candidates):
            endpoint = fleet.endpoint(slot)
            if endpoint is None:
                continue
            if attempt:
                fleet._count("retries")
            hedge_slot = hedge_ep = None
            if attempt == 0:
                hedge_slot, hedge_after_ms = fleet.hedge_plan(
                    slot, candidates)
                hedge_ep = fleet.endpoint(hedge_slot) \
                    if hedge_slot is not None else None
            used = slot
            try:
                conn = self._conn_to(slot, endpoint)
            except _RETRYABLE + (OSError,) as e:
                self._drop_conn(slot)
                fleet.mark_suspect(slot, repr(e))
                last_err = f"{type(e).__name__}: {e}"
                continue
            if hedge_ep is None:
                try:
                    status, payload, up_ctype, retry_after = \
                        self._forward_on(conn, path, body, ctype, rid)
                except socket.timeout:
                    # maybe admitted: do NOT replay on another worker
                    self._drop_conn(slot)
                    fleet._count("errors")
                    return 504, json.dumps(
                        {"error": f"worker {slot} timed out",
                         "request_id": rid}).encode(), \
                        rid_hdr + [("Content-Type", "application/json")]
                except _RETRYABLE + (OSError,) as e:
                    self._drop_conn(slot)
                    fleet.mark_suspect(slot, repr(e))
                    last_err = f"{type(e).__name__}: {e}"
                    continue
            else:
                hedge_holder = {}

                def run_primary(c=conn):
                    return self._forward_on(c, path, body, ctype, rid)

                def run_hedge(ep=hedge_ep):
                    hc = self._fresh_conn(ep)
                    hedge_holder["conn"] = hc
                    return self._forward_on(hc, path, body, ctype, rid)

                res = hedged_call(
                    run_primary, run_hedge,
                    hedge_after=hedge_after_ms / 1e3,
                    timeout=fleet.cfg["timeout_ms"] / 1e3 * 1.5 + 1.0)
                if res["hedged"]:
                    fleet._count_hedge("fired")
                    _flight.rec("fleet.hedge", f"slot{slot}",
                                f"-> slot{hedge_slot} after "
                                f"{hedge_after_ms:.0f}ms")
                if res["hedge_error"] is not None:
                    fleet._count_hedge("failed")
                winner = res["winner"]
                if winner is None:
                    pe = res["primary_error"]
                    self._drop_conn(slot)
                    hc = hedge_holder.get("conn")
                    if hc is not None:
                        try:
                            hc.close()
                        except OSError:
                            pass
                    if isinstance(pe, socket.timeout):
                        # primary timed out and the (already-issued)
                        # hedge could not answer either — 504, nothing
                        # is replayed after a timeout
                        fleet._count("errors")
                        return 504, json.dumps(
                            {"error": f"worker {slot} timed out "
                             "(hedge failed too)",
                             "request_id": rid}).encode(), \
                            rid_hdr + [("Content-Type",
                                        "application/json")]
                    fleet.mark_suspect(slot, repr(pe))
                    if hedge_slot is not None \
                            and res["hedge_error"] is not None:
                        fleet.mark_suspect(hedge_slot,
                                           repr(res["hedge_error"]))
                    last_err = f"{type(pe).__name__}: {pe}" \
                        if pe is not None else "hedged call timed out"
                    continue
                if winner == "hedge":
                    fleet._count_hedge("won")
                    used = hedge_slot
                    # the loser primary still holds the pooled conn: it
                    # may answer later — close it so the pool can't
                    # serve a stale response to the next request
                    self._drop_conn(slot)
                elif res["hedged"]:
                    fleet._count_hedge("lost")
                    hc = hedge_holder.get("conn")
                    if hc is not None:
                        try:
                            hc.close()
                        except OSError:
                            pass
                status, payload, up_ctype, retry_after = res["value"]
            if status == 503 and attempt + 1 < len(candidates):
                # draining worker: the request was NOT admitted there
                continue
            if 200 <= status < 300:
                fleet._count("completed")
                fleet.note_latency(used, (time.monotonic() - t_req) * 1e3)
            hdrs = rid_hdr + [("Content-Type", up_ctype)]
            if status in (429, 503):
                hdrs.append(("Retry-After", retry_after))
            return status, payload, hdrs
        fleet._count("rejects")
        return 503, json.dumps(
            {"error": "every fleet worker refused the request",
             "last_error": last_err, "request_id": rid}).encode(), \
            rid_hdr + [("Content-Type", "application/json"),
                       ("Retry-After", "1")]

    # ---------------------------------------------------------- lifecycle --
    @property
    def port(self):
        return self._httpd.server_address[1]

    @property
    def url(self):
        host = self._httpd.server_address[0]
        return f"http://{host}:{self.port}"

    def start(self):
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._httpd.serve_forever,
                kwargs={"poll_interval": 0.1}, daemon=True,
                name="mxtpu-fleet-router")
            self._thread.start()
        return self

    def close(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None


# --------------------------------------------------------------- the fleet --

_LIVE = weakref.WeakSet()
_collector_installed = False


def live_fleets():
    """ServingFleet instances alive in this process (diagnose)."""
    return list(_LIVE)


class ServingFleet:
    """Supervise N serving workers behind one router (docs/SERVING.md
    "Fleet"). The three control surfaces — per-slot supervision,
    telemetry-driven autoscaling, zero-downtime rollout — run on one
    monitor thread; the router serves on its own HTTP threads."""

    def __init__(self, model_dir, workers=None, *, run_dir=None,
                 policy=None, host="127.0.0.1", port=0, config=None,
                 warmup=True, env=None, cwd=None, name="fleet",
                 bus_dir=None, hosts=None, popen=None):
        import tempfile

        cfg = dict(effective())
        if isinstance(config, str):
            cfg.update(_parse(config))
        elif config:
            for k, v in config.items():
                cfg[k] = _coerce(k, v)
        if policy is not None:
            cfg["policy"] = _coerce("policy", policy)
        self.cfg = cfg
        self.name = str(name)
        self.model_dir = os.fspath(model_dir)
        self.run_dir = os.fspath(
            run_dir or tempfile.mkdtemp(prefix="mxtpu_fleet_"))
        os.makedirs(self.run_dir, exist_ok=True)
        self._initial_workers = max(1, int(cfg["min"]
                                           if workers is None else workers))
        self._host, self._port = host, int(port)
        self._warmup = bool(warmup)
        self.generation = 0
        self.state = "idle"
        self._gen_dirs = {}        # generation -> model dir
        self._desired = {}         # slot -> generation
        self._next_slot = 0
        self._routable = []        # slots taking traffic right now
        self._endpoints = {}       # slot -> (host, port)
        self._suspect = {}         # slot -> monotonic deadline
        self._rr = 0
        self._ring = HashRing()
        self.rollouts = []
        self._counters = {"requests": 0, "completed": 0, "retries": 0,
                          "rejects": 0, "errors": 0}
        self._count_lock = threading.Lock()
        self._hedge = HedgeGovernor(cfg, self._slot_locality)
        self._scaler = Autoscaler(cfg)
        self._last_completed = None   # (t_mono, fleet completed total)
        self._last_sample = {}
        self._lock = threading.RLock()      # census + rollout/scale
        self._stop_evt = threading.Event()
        self._monitor = None
        self._router = None
        self._summary_at = 0.0

        worker_env = dict(env or {})
        worker_env.setdefault("MXNET_TPU_GANG_BEAT", str(cfg["beat"]))
        # workers must find this package without an installed dist
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        worker_env["PYTHONPATH"] = pkg_root + os.pathsep + \
            os.environ.get("PYTHONPATH", "")
        # a shared persistent compile cache is what makes rollout cheap:
        # generation N+1 LOADS the ladder the first generation compiled.
        # Workers inherit MXNET_TPU_CACHE_DIR / JAX_COMPILATION_CACHE_DIR
        # from this process's environment (or take compile.py's fixed
        # on-TPU default); no cache path is derived from the run dir,
        # which may be a fresh temporary name that would never hit
        # diagnose run next to the fleet finds the run dir through this
        worker_env.setdefault("MXTPU_FLEET_DIR", self.run_dir)
        # live weight streaming: every worker of every generation
        # subscribes to the same bus (the trainer's publish_to target)
        self.bus_dir = os.fspath(bus_dir) if bus_dir \
            else os.environ.get("MXTPU_MODELBUS_DIR")
        if self.bus_dir:
            worker_env.setdefault("MXTPU_MODELBUS_DIR", self.bus_dir)

        from .. import elastic as _elastic

        self._worker_env = worker_env
        self.hosts = normalize_hosts(hosts) if hosts else None
        self._slot_host = {}       # slot -> host name (multi-host only)
        if self.hosts is None:
            self._sup = _elastic.ServingSupervisor(
                self._command_for, self.run_dir, grace=cfg["grace"],
                dead_after=cfg["dead_after"],
                max_restarts=cfg["restarts"],
                env=worker_env, cwd=cwd, popen=popen)
        else:
            self._by_host = {h["name"]: h for h in self.hosts}
            sups = {}
            for h in self.hosts:
                h["run_dir"] = os.path.join(self.run_dir,
                                            f"host-{h['name']}")
                os.makedirs(h["run_dir"], exist_ok=True)
                henv = dict(worker_env)
                henv.update(h["env"])
                sups[h["name"]] = _elastic.ServingSupervisor(
                    self._host_command_for(h), h["run_dir"],
                    grace=cfg["grace"], dead_after=cfg["dead_after"],
                    max_restarts=cfg["restarts"], env=henv,
                    cwd=(h["cwd"] if not h["ssh"] else None) or cwd,
                    popen=popen)
            self._sup = _HostPlane(sups, self._slot_host)

        from ..telemetry import fleet as _tfleet

        _tfleet.install(self.run_dir)
        _install_collector()
        _LIVE.add(self)
        self._t_start = time.monotonic()

    # -------------------------------------------------------- worker cmds --
    def _command_for(self, slot, generation):
        cmd = [sys.executable, "-m", "mxnet_tpu.serving.worker",
               "--model-dir", self._gen_dirs[generation],
               "--slot", str(slot), "--generation", str(generation)]
        if not self._warmup:
            cmd.append("--no-warmup")
        return cmd

    def _host_command_for(self, host):
        """The per-host supervisor's command factory: the worker argv
        carries run-dir/slot/generation/bind-address EXPLICITLY (an ssh
        child does not inherit the local supervisor env), and an ssh
        host wraps it in the same ``ssh -tt ... exec env ...`` launch
        the gang supervisor uses — so a remote worker still heartbeats
        and announces into its (shared-filesystem) host dir."""

        def command_for(slot, generation):
            argv = [sys.executable, "-m", "mxnet_tpu.serving.worker",
                    "--model-dir", self._gen_dirs[generation],
                    "--run-dir", host["run_dir"],
                    "--slot", str(slot),
                    "--generation", str(generation),
                    "--host", host["advertise"]]
            if not self._warmup:
                argv.append("--no-warmup")
            if not host["ssh"]:
                return argv
            from .. import elastic as _elastic

            env = dict(self._worker_env)
            env.update(host["env"])
            env.update({"MXTPU_GANG_DIR": host["run_dir"],
                        "MXTPU_WORKER_ID": str(slot),
                        "MXTPU_GANG_GENERATION": str(generation),
                        "MXNET_TPU_PREEMPT": "1"})
            return _elastic._ssh_argv(host["ssh"], env, argv,
                                      cwd=host["cwd"])

        return command_for

    def _pick_host(self):
        """Least-populated host wins the next slot (definition order
        breaks ties) — the fleet stays balanced through scale-up,
        rollout and per-slot restarts alike. Caller holds ``_lock``."""
        counts = {h["name"]: 0 for h in self.hosts}
        for s, hn in self._slot_host.items():
            if s in self._desired and hn in counts:
                counts[hn] += 1
        return min(self.hosts, key=lambda h: counts[h["name"]])["name"]

    def _spawn(self, generation):
        with self._lock:
            slot = self._next_slot
            self._next_slot += 1
            self._desired[slot] = int(generation)
            if self.hosts is not None:
                self._slot_host[slot] = self._pick_host()
        self._sup.spawn(slot, generation)
        return slot

    def _slot_locality(self, slot):
        if self.hosts is None:
            return "local"
        h = self._by_host.get(self._slot_host.get(slot))
        return h["locality"] if h else "local"

    def _slot_ssh(self, slot):
        if self.hosts is None:
            return None
        h = self._by_host.get(self._slot_host.get(slot))
        return h["ssh"] if h else None

    # ---------------------------------------------------------- lifecycle --
    def start(self, wait_ready=True, timeout=None):
        """Spawn the initial generation, start the router + monitor;
        with ``wait_ready`` (default) block until every worker passed
        the health gate (or raise :class:`FleetError`)."""
        with self._lock:
            if self.state != "idle":
                return self
            self.state = "starting"
            self.generation = 1
            self._gen_dirs[1] = self.model_dir
        for _ in range(self._initial_workers):
            self._spawn(1)
        self._router = _RouterFront(self, self._host, self._port).start()
        self._monitor = threading.Thread(target=self._monitor_loop,
                                         daemon=True,
                                         name="mxtpu-fleet-monitor")
        self._monitor.start()
        _flight.rec("fleet.start", self.name,
                    f"{self._initial_workers} worker(s) @ {self.url}")
        if wait_ready:
            self.wait_ready(timeout=timeout)
        with self._lock:
            if self.state == "starting":
                self.state = "serving"
        self._write_summary(force=True)
        return self

    @property
    def url(self):
        return self._router.url if self._router is not None else None

    def wait_ready(self, timeout=None, generation=None):
        """Block until every desired worker of `generation` (default:
        the active one) passes the health gate; FleetError on timeout."""
        deadline = time.monotonic() + (self.cfg["ready_timeout"]
                                       if timeout is None else timeout)
        while True:
            gen = self.generation if generation is None else generation
            want = [s for s, g in self._desired.items() if g == gen]
            ready = self._gated_ready(want)
            if want and len(ready) == len(want):
                # publish to the router NOW — the monitor's next pass
                # may be a poll period away and the caller is about to
                # send traffic
                self._refresh()
                return ready
            if time.monotonic() >= deadline:
                anns = _worker.read_workers(self.run_dir)
                states = {s: (anns.get(s) or {}).get("state", "absent")
                          for s in want}
                raise FleetError(
                    f"fleet workers not ready within the deadline: "
                    f"{states}; supervisor: "
                    f"{ {s: r['state'] for s, r in self._sup.census().items()} }")
            time.sleep(0.05)

    def stop(self, drain=True):
        """Retire every worker (graceful drain by default), stop the
        router + monitor, write the final summary."""
        with self._lock:
            if self.state in ("stopped", "idle"):
                self.state = "stopped"
                return
            self.state = "stopping"
        self._stop_evt.set()
        if self._monitor is not None:
            self._monitor.join(timeout=10.0)
        self._sup.stop_all(graceful=drain)
        if self._router is not None:
            self._router.close()
        with self._lock:
            self.state = "stopped"
            self._routable = []  # _desired kept: the final fleet.json
            # census is the diagnose report's post-mortem view
        _flight.rec("fleet.stop", self.name)
        self._write_summary(force=True)

    # ------------------------------------------------------------ routing --
    def _gated_ready(self, slots):
        """Slots (of the given census) passing the announce health gate
        with a live, pid-matching process. An ssh-placed slot relaxes
        the pid equality (the announce carries the REMOTE worker pid,
        the census the local ssh client's) — generation match + a live
        supervised process still gate it."""
        anns = _worker.read_workers(self.run_dir)
        census = self._sup.census()
        out = []
        for slot in slots:
            rec = census.get(slot)
            ann = anns.get(slot)
            pid_ok = ann is not None and rec is not None and (
                ann.get("pid") == rec.get("pid")
                or self._slot_ssh(slot) is not None)
            if (rec and rec.get("alive") and gate_ready(ann) and pid_ok
                    and ann.get("generation") == rec.get("generation")):
                out.append(slot)
                self._endpoints[slot] = (ann.get("host", "127.0.0.1"),
                                         int(ann["port"]))
        return out

    def _refresh(self):
        gen = self.generation
        want = sorted(s for s, g in self._desired.items() if g == gen)
        ready = self._gated_ready(want)
        now = time.monotonic()
        self._suspect = {s: t for s, t in self._suspect.items() if t > now}
        routable = [s for s in ready if s not in self._suspect]
        self._routable = routable or ready
        if self.cfg["policy"] == "hash":
            self._ring.rebuild(self._routable)

    def pick(self, model):
        """Policy-ordered candidate slots for one request: the routing
        policy (locality-aware when multi-host) orders them, then
        flagged persistent stragglers are stable-moved to the tail —
        they stay reachable (failover, hedges) but stop being anyone's
        first choice."""
        self._rr += 1
        depths = None
        if self.cfg["policy"] == "least_loaded":
            depths = {s: m.get("queue_depth")
                      for s, m in self._last_sample.get(
                          "per_worker", {}).items()}
        localities, penalty = None, 0.0
        if self.hosts is not None:
            localities = {s: self._slot_locality(s)
                          for s in self._routable}
            if any(v == "remote" for v in localities.values()):
                penalty = self._hedge.remote_penalty()
            else:
                localities = None
        order = order_candidates(self.cfg["policy"], model,
                                 self._routable, depths=depths,
                                 rr=self._rr, ring=self._ring,
                                 localities=localities,
                                 remote_penalty=penalty)
        return self._hedge.reorder(order, self._rr)

    def endpoint(self, slot):
        return self._endpoints.get(slot)

    # ------------------------------------------------- latency + hedging --
    def note_latency(self, slot, ms):
        """One completed router request against `slot` took `ms`
        end-to-end: feeds the hedge-threshold p99 ring, the per-slot
        straggler EWMAs and the per-locality spill penalty."""
        self._hedge.note(slot, ms)

    def hedge_plan(self, slot, candidates):
        """(hedge slot, threshold ms) for a first attempt against
        `slot`, or (None, None) — see :meth:`HedgeGovernor.plan`."""
        return self._hedge.plan(slot, candidates, self.endpoint)

    def _count_hedge(self, outcome):
        self._hedge.count(outcome)

    def mark_suspect(self, slot, why=""):
        """A connection-level failure against `slot`: deprioritize it
        until the monitor re-verifies (or the supervisor respawns it)."""
        self._suspect[slot] = time.monotonic() + 1.0
        self._routable = [s for s in self._routable if s != slot]
        _flight.rec("fleet.suspect", f"slot{slot}", why)

    def _count(self, key, n=1):
        with self._count_lock:
            self._counters[key] = self._counters.get(key, 0) + n

    def models(self):
        """The served model list (from any ready worker's announce)."""
        anns = _worker.read_workers(self.run_dir)
        for slot in self._routable:
            ann = anns.get(slot)
            if ann and ann.get("models"):
                return {"models": ann["models"],
                        "generation": ann.get("generation")}
        return {"models": [], "generation": self.generation}

    # ------------------------------------------------------------ scaling --
    def scale_to(self, n, reason="manual"):
        """Grow/shrink the active generation to `n` workers (scale-up
        spawns; scale-down drains the highest slots through exit 75)."""
        n = int(n)
        if n < 1:
            raise ValueError(f"fleet cannot scale below 1 worker (got {n})")
        with self._lock:
            gen = self.generation
            active = sorted(s for s, g in self._desired.items()
                            if g == gen)
            if n > len(active):
                added = [self._spawn(gen) for _ in range(n - len(active))]
                _flight.rec("fleet.scale", "up",
                            f"{len(active)} -> {n} ({reason})")
                _logger.info("fleet: scale up %d -> %d (%s; slots %s)",
                             len(active), n, reason, added)
            elif n < len(active):
                dropped = active[n:]
                for slot in dropped:
                    self._desired.pop(slot, None)
                    self._sup.drain_slot(slot, reason=f"scale-down "
                                                      f"({reason})")
                _flight.rec("fleet.scale", "down",
                            f"{len(active)} -> {n} ({reason})")
                _logger.info("fleet: scale down %d -> %d (%s; drained "
                             "%s)", len(active), n, reason, dropped)
        self._write_summary(force=True)
        return n

    def _sample(self, now):
        gen = self.generation
        active = {s for s, g in self._desired.items() if g == gen}
        per = worker_metrics(self.run_dir, slots=active)
        per = {s: m for s, m in per.items()
               if m.get("generation") == gen}
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
            dt = now - t0
            if dt > 0:
                rps = max(0.0, (completed - c0) / dt)
        self._last_completed = (now, completed)
        sample = {"queue_depth": max(depths) if depths else None,
                  "p99_ms": max(p99s) if p99s else None,
                  "fill": max(fills) if fills else None,
                  "rps": rps, "completed": completed,
                  "per_worker": per}
        self._last_sample = sample
        return sample

    def _autoscale_tick(self, now):
        sample = self._sample(now)
        # straggler flags ride the same cadence (one streak advance per
        # interval), autoscaling enabled or not — the router's hedge
        # threshold and candidate ordering depend on them either way
        self._hedge.update_stragglers(self._routable)
        if self.cfg["max"] <= self.cfg["min"]:
            return  # fixed-size fleet: sampling still feeds the router
        if self.state != "serving":
            return
        with self._lock:
            active = sum(1 for g in self._desired.values()
                         if g == self.generation)
        direction, rec = self._scaler.decide(sample, active, now=now)
        if direction == "up":
            self.scale_to(min(self.cfg["max"], active + 1),
                          reason=f"autoscale: {rec['reason']}")
        elif direction == "down":
            self.scale_to(max(self.cfg["min"], active - 1),
                          reason=f"autoscale: {rec['reason']}")
        if direction:
            _flight.rec("fleet.autoscale", direction, rec["reason"])

    # ------------------------------------------------------------ rollout --
    def rollout(self, new_model_dir, timeout=None):
        """Zero-downtime model swap: spawn a generation-N+1 worker set
        from `new_model_dir` (warm from the shared disk compile cache),
        health-gate every new worker (announce census with zero pending
        compiles + live ``/healthz``), shift router traffic atomically,
        then drain generation N through exit 75. Returns the rollout
        record; raises :class:`FleetError` (old generation untouched)
        when the gate times out."""
        import urllib.request

        with self._lock:
            if self.state != "serving":
                raise FleetError(
                    f"rollout needs a serving fleet (state "
                    f"{self.state!r})")
            old_gen = self.generation
            new_gen = old_gen + 1
            self._gen_dirs[new_gen] = os.fspath(new_model_dir)
            old_slots = sorted(s for s, g in self._desired.items()
                               if g == old_gen)
            n = max(1, len(old_slots))
            # the autoscaler sits out the swap (state-gated): a census
            # change mid-rollout would race the generation accounting
            self.state = "rolling-out"
        rec = {"generation": new_gen,
               "model_dir": os.fspath(new_model_dir),
               "from_generation": old_gen, "t_start": time.time(),
               "workers": [], "drained": {}, "state": "spawning"}
        _flight.rec("fleet.rollout", f"gen{new_gen}",
                    os.fspath(new_model_dir))
        _logger.info("fleet: rollout -> generation %d (%s), %d worker(s)",
                     new_gen, new_model_dir, n)
        new_slots = [self._spawn(new_gen) for _ in range(n)]
        rec["workers"] = new_slots
        # ---- health gate: announce-ready + zero pending compiles + a
        # live /healthz answer from every new worker
        deadline = time.monotonic() + (self.cfg["ready_timeout"]
                                       if timeout is None else timeout)
        rec["state"] = "health-gate"
        while True:
            ready = self._gated_ready(new_slots)
            if len(ready) == len(new_slots):
                healthy = []
                for slot in ready:
                    host, port = self._endpoints[slot]
                    try:
                        with urllib.request.urlopen(
                                f"http://{host}:{port}/healthz",
                                timeout=2.0) as resp:
                            ok = json.loads(resp.read()).get(
                                "status") == "ok"
                    except (OSError, ValueError):
                        ok = False
                    if ok:
                        healthy.append(slot)
                if len(healthy) == len(new_slots):
                    break
            if time.monotonic() >= deadline:
                anns = _worker.read_workers(self.run_dir)
                states = {
                    s: {"state": (anns.get(s) or {}).get("state",
                                                         "absent"),
                        "pending_compiles":
                        (anns.get(s) or {}).get("pending_compiles")}
                    for s in new_slots}
                with self._lock:
                    for slot in new_slots:
                        self._desired.pop(slot, None)
                        self._sup.drain_slot(slot,
                                             reason="rollout aborted")
                rec["state"] = "aborted"
                rec["gate_failures"] = states
                self.rollouts.append(rec)
                with self._lock:
                    self.generation = old_gen
                    self._gen_dirs.pop(new_gen, None)
                    self.state = "serving"
                self._write_summary(force=True)
                raise FleetError(
                    f"rollout to generation {new_gen} aborted: health "
                    f"gate not passed within the deadline — {states} "
                    "(the old generation keeps serving)")
            time.sleep(0.05)
        # ---- atomic traffic shift, then drain the old generation
        with self._lock:
            self.generation = new_gen
        self._refresh()
        rec["state"] = "draining-old"
        rec["t_shift"] = time.time()
        _flight.rec("fleet.shift", f"gen{new_gen}",
                    f"{len(new_slots)} worker(s) live")
        with self._lock:
            for slot in old_slots:
                self._desired.pop(slot, None)
                self._sup.drain_slot(slot,
                                     reason=f"rollout gen{new_gen}")
        drain_deadline = time.monotonic() + self.cfg["drain_timeout"]
        while time.monotonic() < drain_deadline:
            self._sup.poll()
            left = [s for s in old_slots if s in self._sup.slots]
            if not left:
                break
            time.sleep(0.05)
        for ev in self._sup.events:
            if ev["kind"] in ("drained", "drain_killed") \
                    and ev["slot"] in old_slots:
                rec["drained"][str(ev["slot"])] = ev.get("exit_code")
        anns = _worker.read_workers(self.run_dir)
        rec["old_final"] = {
            str(s): {k: (anns.get(s) or {}).get(k)
                     for k in ("state", "admitted", "answered", "failed",
                               "drained")}
            for s in old_slots}
        rec["state"] = "done"
        rec["t_done"] = time.time()
        self.rollouts.append(rec)
        with self._lock:
            self.state = "serving"
        _logger.info("fleet: rollout to generation %d complete (old "
                     "generation exits: %s)", new_gen, rec["drained"])
        self._write_summary(force=True)
        return rec

    # ------------------------------------------------------------ monitor --
    def _monitor_loop(self):
        next_tick = 0.0
        while not self._stop_evt.is_set():
            try:
                self._sup.poll()
                self._refresh()
                now = time.monotonic()
                if now >= next_tick:
                    next_tick = now + self.cfg["interval"]
                    self._autoscale_tick(now)
                self._write_summary()
            except Exception:
                _logger.exception("fleet: monitor pass failed (fleet "
                                  "keeps serving)")
            self._stop_evt.wait(0.05)

    # -------------------------------------------------------------- state --
    def stats(self, light=False):
        """The fleet's aggregate observability snapshot (router /stats,
        fleet.json, diagnose)."""
        with self._lock:
            desired = dict(self._desired)
            gen = self.generation
        base = {"name": self.name, "state": self.state,
                "generation": gen, "policy": self.cfg["policy"],
                "desired": sum(1 for g in desired.values() if g == gen),
                "ready": len(self._routable)}
        if light:
            return base
        census = self._sup.census()
        anns = _worker.read_workers(self.run_dir)
        per = self._last_sample.get("per_worker", {})
        workers = {}
        for slot, g in sorted(desired.items()):
            rec = census.get(slot) or {}
            ann = anns.get(slot) or {}
            m = per.get(slot) or {}
            workers[str(slot)] = {
                "generation": g, "state": rec.get("state"),
                "alive": rec.get("alive"), "pid": rec.get("pid"),
                "restarts": rec.get("restarts"),
                "port": ann.get("port"), "ready": gate_ready(ann),
                "models": ann.get("models"),
                "queue_depth": m.get("queue_depth"),
                "p99_ms": m.get("p99_ms"), "rps": m.get("rps"),
                "shard_age_s": m.get("age_s"),
                "model_bus": ann.get("model_bus"),
                "host": self._slot_host.get(slot),
                "locality": self._slot_locality(slot),
                "straggler": slot in self._hedge.stragglers}
        with self._count_lock:
            counters = dict(self._counters)
        hedge_state = self._hedge.describe()
        base.update({
            "url": self.url, "run_dir": self.run_dir,
            "bus_dir": self.bus_dir,
            "uptime_s": round(time.monotonic() - self._t_start, 1),
            "workers": workers,
            "hosts": None if self.hosts is None else [
                {"name": h["name"], "ssh": h["ssh"],
                 "locality": h["locality"],
                 "advertise": h["advertise"],
                 "slots": sorted(
                     s for s, hn in self._slot_host.items()
                     if hn == h["name"] and s in desired)}
                for h in self.hosts],
            "router": counters,
            "hedges": hedge_state["hedges"],
            "stragglers": hedge_state["stragglers"],
            "router_latency": hedge_state["router_latency"],
            "autoscaler": self._scaler.describe(),
            "sample": {k: self._last_sample.get(k) for k in
                       ("queue_depth", "p99_ms", "fill", "rps")},
            "rollouts": [
                {k: v for k, v in r.items() if k != "old_final"}
                for r in self.rollouts[-8:]],
            "supervisor": {"restarts_total": self._sup.restarts_total,
                           "drained_total": self._sup.drained_total},
        })
        return base

    def describe(self):
        """stats() + config + supervisor events (fleet.json)."""
        out = self.stats()
        out["config"] = dict(self.cfg)
        out["events"] = list(self._sup.events[-64:])
        return out

    def _write_summary(self, force=False):
        now = time.monotonic()
        if not force and now - self._summary_at < 1.0:
            return
        self._summary_at = now
        from .. import elastic as _elastic

        try:
            rec = self.describe()
            rec["updated"] = time.time()
            _elastic._atomic_json(
                os.path.join(self.run_dir, "fleet.json"), rec)
        except OSError as e:
            _logger.warning("fleet: could not write fleet.json: %s", e)


# --------------------------------------------------- telemetry collector ---

def _collect_serving_fleet():
    """Scrape-time gauges for the most recent live fleet in this
    process: rollout generation, census, fleet-wide completion rate and
    the router/autoscale counters (the per-worker gauge re-exports come
    from :mod:`mxnet_tpu.telemetry.fleet`'s shard collector)."""
    from ..telemetry import registry as _registry

    fleets = sorted(_LIVE, key=lambda f: f._t_start)
    if not fleets:
        return
    fl = fleets[-1]
    st = fl.stats(light=True)
    _registry.gauge("mxtpu_fleet_generation",
                    "Active fleet model generation (bumps per rollout)"
                    ).set(st["generation"])
    _registry.gauge("mxtpu_fleet_workers_desired",
                    "Workers the fleet wants in the active generation"
                    ).set(st["desired"])
    _registry.gauge("mxtpu_fleet_workers_ready",
                    "Workers currently routable").set(st["ready"])
    rps = fl._last_sample.get("rps")
    _registry.gauge("mxtpu_fleet_rps",
                    "Fleet-wide completion rate over the last "
                    "autoscaler interval").set(rps or 0.0)
    router = _registry.counter("mxtpu_fleet_router_requests_total",
                               "Router requests by outcome",
                               labels=("outcome",))
    with fl._count_lock:
        counters = dict(fl._counters)
    for outcome, n in counters.items():
        router.set_total(n, outcome)
    hedge = _registry.counter(
        "mxtpu_fleet_hedges_total",
        "Hedged router requests by outcome (fired/won/lost/failed)",
        labels=("outcome",))
    with fl._hedge._lock:
        hedges = dict(fl._hedge.counters)
    for outcome, n in hedges.items():
        hedge.set_total(n, outcome)
    _registry.gauge("mxtpu_fleet_stragglers",
                    "Worker slots currently flagged as persistent "
                    "router-latency stragglers").set(
                        len(fl._hedge.stragglers))
    scale = _registry.counter("mxtpu_fleet_autoscale_total",
                              "Autoscaler actions", labels=("direction",))
    for direction, n in fl._scaler.decisions.items():
        scale.set_total(n, direction)
    _registry.counter("mxtpu_fleet_worker_restarts_total",
                      "Fleet worker slot restarts").set_total(
                          fl._sup.restarts_total)
    _registry.counter("mxtpu_fleet_workers_drained_total",
                      "Deliberately drained fleet workers (rollout / "
                      "scale-down / stop)").set_total(
                          fl._sup.drained_total)


def _install_collector():
    global _collector_installed
    if _collector_installed:
        return
    _collector_installed = True
    from ..telemetry import export as _export

    _export.register_collector("serving_fleet", _collect_serving_fleet)
