#!/usr/bin/env python
"""Diagnose the runtime environment (parity: tools/diagnose.py — platform,
package versions, hardware, environment variables; the script users attach
to bug reports).

    python tools/diagnose.py            # human-readable report
    python tools/diagnose.py --json     # one machine-readable JSON doc
    python tools/diagnose.py --gc       # also prune the compile cache

Every section both prints its human text and contributes a dict to the
``--json`` document (CI scrapers consume the JSON; humans the text —
same collection pass either way).
"""
import importlib
import json
import os
import platform
import sys
import time

# `python tools/diagnose.py` puts tools/ (not the repo root) on sys.path;
# the framework checks need the package importable either way
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_ECHO = True


def _p(*args, **kwargs):
    if _ECHO:
        print(*args, **kwargs)


def check_python():
    _p("----------Python Info----------")
    out = {"version": platform.python_version(),
           "compiler": platform.python_compiler(),
           "build": list(platform.python_build()),
           "arch": list(platform.architecture())}
    _p("Version      :", out["version"])
    _p("Compiler     :", out["compiler"])
    _p("Build        :", tuple(out["build"]))
    _p("Arch         :", tuple(out["arch"]))
    return out


def check_pip():
    _p("------------Pip Info-----------")
    try:
        import pip

        _p("Version      :", pip.__version__)
        return {"version": pip.__version__}
    except ImportError:
        _p("No corresponding pip install for current python.")
        return {"version": None}


def check_framework():
    _p("---------Framework Info--------")
    out = {}
    try:
        import mxnet_tpu as mx

        out["version"] = mx.__version__
        out["directory"] = os.path.dirname(mx.__file__)
        _p("Version      :", out["version"])
        _p("Directory    :", out["directory"])
        from mxnet_tpu import runtime

        feats = runtime.Features()
        on = [name for name in feats.keys() if feats.is_enabled(name)]
        out["features"] = sorted(on)
        _p("Features     :", ", ".join(sorted(on)))
    except ImportError as e:
        out["error"] = str(e)
        _p("framework import failed:", e)
    return out


def check_deps():
    _p("--------Dependency Info--------")
    out = {}
    for name in ("jax", "jaxlib", "numpy", "flax", "optax"):
        try:
            mod = importlib.import_module(name)
            out[name] = getattr(mod, "__version__", "unknown")
            _p(f"{name:<13}:", out[name])
        except ImportError:
            out[name] = None
            _p(f"{name:<13}: not installed")
    return out


def check_hardware():
    _p("---------Hardware Info---------")
    out = {"machine": platform.machine(), "platform": platform.platform()}
    _p("Machine      :", out["machine"])
    _p("Platform     :", out["platform"])
    try:
        import jax

        t0 = time.time()
        devices = jax.devices()
        out["devices"] = [str(d) for d in devices]
        out["probe_s"] = round(time.time() - t0, 2)
        out["process_count"] = jax.process_count()
        _p("Devices      :", devices, f"(probe {out['probe_s']:.2f}s)")
        _p("Processes    :", out["process_count"])
    except Exception as e:  # no backend could start
        out["device_probe_error"] = f"{type(e).__name__}: {e}"
        _p("Device probe failed:", e)
    return out


def check_environment():
    _p("----------Environment----------")
    out = {}
    for k, v in sorted(os.environ.items()):
        if k.startswith(("MXNET_", "MXTPU_", "JAX_", "XLA_", "TPU_",
                         "DMLC_", "OMP_", "LD_", "PYTHON")):
            out[k] = v
            _p(f"{k}={v}")
    return out


def check_analysis():
    """The static-analysis knobs (docs/ANALYSIS.md) with effective state."""
    _p("---------Analysis Knobs--------")
    out = {"MXNET_TPU_VERIFY": os.environ.get("MXNET_TPU_VERIFY"),
           "MXNET_TPU_SANITIZE": os.environ.get("MXNET_TPU_SANITIZE"),
           "MXNET_TPU_DISTCHECK": os.environ.get("MXNET_TPU_DISTCHECK")}
    _p(f"MXNET_TPU_VERIFY={out['MXNET_TPU_VERIFY'] or '<unset>'}  "
       "(graph verifier inside simple_bind; on unless 0)")
    _p(f"MXNET_TPU_SANITIZE={out['MXNET_TPU_SANITIZE'] or '<unset>'}  "
       "(sync-hazard sanitizer; off unless 1)")
    _p(f"MXNET_TPU_DISTCHECK={out['MXNET_TPU_DISTCHECK'] or '<unset>'}  "
       "(distributed-correctness analyzer: ShardedTrainer auto-check, "
       "donation poisoning, compile-cache tracking; on unless 0)")
    try:
        from mxnet_tpu.analysis import distcheck as _dc
        from mxnet_tpu.analysis import sanitize as _san
        from mxnet_tpu.analysis.verify import verify_enabled

        out["effective"] = {"verify": verify_enabled(),
                            "sanitize": bool(_san.ACTIVE),
                            "distcheck": _dc.enabled()}
        _p("effective     : verify=%s sanitize=%s distcheck=%s"
           % (verify_enabled(), _san.ACTIVE, _dc.enabled()))
    except ImportError as e:
        out["error"] = str(e)
        _p("analysis import failed:", e)
    return out


def check_concur():
    """Concurrency analyzer (docs/ANALYSIS.md "Concurrency checks"):
    the static lock-graph census over the package (locks, ordered
    edges, current findings), the suppression counts, the torn-file
    seam registry, and the runtime lock witness state including the
    last inversion it saw."""
    _p("---------Concurrency-----------")
    out = {"MXNET_TPU_CONCUR": os.environ.get("MXNET_TPU_CONCUR"),
           "MXNET_TPU_CONCUR_TRACE":
               os.environ.get("MXNET_TPU_CONCUR_TRACE")}
    _p(f"MXNET_TPU_CONCUR={out['MXNET_TPU_CONCUR'] or '<unset>'}  "
       "(lock-order / shared-state / torn-file passes; on unless 0)")
    _p(f"MXNET_TPU_CONCUR_TRACE={out['MXNET_TPU_CONCUR_TRACE'] or '<unset>'}"
       "  (arm the runtime lock witness at import; off unless 1)")
    try:
        from mxnet_tpu.analysis import concur
    except ImportError as e:
        out["error"] = str(e)
        _p("concur import failed:", e)
        return out
    out["enabled"] = concur.enabled()
    if not concur.enabled():
        _p("analyzer      : disabled (MXNET_TPU_CONCUR=0)")
        return out
    model = concur.scan()
    edges = sum(len(v) for v in model.edges.values())
    issues = concur.run_static()
    out["graph"] = {"files": len(model.files),
                    "locks": len(model.locks), "edges": edges}
    out["suppressions"] = dict(model.suppressions)
    out["findings"] = [f"[{i.severity}:{i.code}] {i.node}"
                       for i in issues]
    _p(f"lock graph    : {len(model.locks)} locks across "
       f"{len(model.files)} modules, {edges} ordered edges")
    _p(f"findings      : {len(issues)} "
       f"({sum(1 for i in issues if i.is_error)} errors) — "
       f"{out['findings'][:5] or 'clean'}")
    _p(f"suppressions  : {model.suppressions['atomic']} "
       f"'# concur: atomic', {model.suppressions['torn']} "
       f"'# concur: torn-ok'")
    out["torn_seams"] = sorted(
        f"{mk}.{qn}" if mk else qn for mk, qn in concur.TORN_SEAMS)
    _p(f"torn-file seams: {len(out['torn_seams'])} registered atomic "
       "writers (concur.TORN_SEAMS)")
    wit = concur.witness_state()
    out["witness"] = wit
    if wit["armed"]:
        _p(f"lock witness  : ARMED — {wit['wrapped']} locks wrapped, "
           f"{wit['ring']} acquisitions in the ring, "
           f"{wit['pairs']} ordered pairs")
    else:
        _p("lock witness  : disarmed (concur.trace_locks() or "
           "MXNET_TPU_CONCUR_TRACE=1 to arm)")
    _p(f"last inversion: {wit['last_inversion'] or 'none'}")
    return out


def check_compile_cache(gc=False):
    """Compile-cache health: the unified compile service's per-site
    hit/miss/compile-ms stats (mxnet_tpu.compile), the persistent on-disk
    cache census (location / entries / bytes / staleness), the most recent
    AOT warmup-manifest replay, and the analysis.distcheck pass-4
    recompile-churn report. In-memory stats are empty outside a training
    process; the on-disk census and last-warmup record persist. With
    ``gc=True`` (the ``--gc`` flag), stale-fingerprint and corrupt disk
    entries are pruned."""
    _p("--------Compile Cache----------")
    out = {"MXNET_TPU_CACHE_DIR": os.environ.get("MXNET_TPU_CACHE_DIR"),
           "MXNET_TPU_COMPILE_SERVICE":
               os.environ.get("MXNET_TPU_COMPILE_SERVICE")}
    try:
        from mxnet_tpu import compile as _compile

        _p(f"MXNET_TPU_CACHE_DIR="
           f"{out['MXNET_TPU_CACHE_DIR'] or '<unset>'}  "
           "(persistent executable cache; memory-only when unset)")
        _p(f"MXNET_TPU_COMPILE_SERVICE="
           f"{out['MXNET_TPU_COMPILE_SERVICE'] or '<unset>'}  "
           "(0 bypasses the service — raw jax.jit)")
        svc = _compile.stats()
        out["service"] = svc
        if svc:
            _p(f"{'service site':<16s} {'hits':>7s} {'misses':>7s} "
               f"{'disk':>6s} {'compiles':>9s} {'compile_ms':>11s} "
               f"{'load_ms':>8s}")
            for site, st in svc.items():
                _p(f"{site:<16s} {st['hits']:>7d} {st['misses']:>7d} "
                   f"{st['disk_hits']:>6d} {st['compiles']:>9d} "
                   f"{st['compile_ms']:>11.1f} {st['load_ms']:>8.1f}")
        else:
            _p("service stats : none this process")
        rep = _compile.disk_report()
        out["disk"] = rep
        if rep["dir"] is None:
            _p("disk cache    : disabled (set MXNET_TPU_CACHE_DIR)")
        else:
            _p(f"disk cache    : {rep['dir']}")
            _p(f"  fingerprint : {rep['fingerprint']}")
            _p(f"  entries     : {rep['entries']} "
               f"({rep['bytes']} bytes), xla-native "
               f"{rep['xla_entries']}")
            if rep["stale_entries"]:
                _p(f"  stale       : {rep['stale_entries']} entries "
                   f"({rep['stale_bytes']} bytes) from other "
                   "fingerprints — prune with --gc")
            if gc:
                gced = _compile.gc_cache()
                out["gc"] = gced
                _p(f"  gc          : removed {gced['removed_stale']} "
                   f"stale + {gced['removed_corrupt']} corrupt "
                   f"({gced['bytes_freed']} bytes freed)")
        warm = _compile.last_warmup()
        out["last_warmup"] = warm
        if warm is None:
            _p("last warmup   : none recorded")
        else:
            _p(f"last warmup   : {warm.get('entries', 0)} entries — "
               f"{warm.get('compiled', 0)} compiled, "
               f"{warm.get('disk', 0)} from disk, "
               f"{warm.get('cached', 0)} cached, "
               f"{warm.get('pending', 0)} pending, "
               f"{len(warm.get('errors', []))} errors")
    except ImportError as e:
        out["error"] = str(e)
        _p("compile service import failed:", e)
    try:
        from mxnet_tpu.analysis import distcheck as _dc

        stats = _dc.cache_stats()
        out["cache_tracking"] = bool(_dc.CACHE_TRACK)
        out["cache_stats"] = {f"{kind}:{site}": rec
                              for (kind, site), rec in stats.items()}
        if not stats:
            _p("no cache activity recorded "
               "(tracking %s; MXNET_TPU_DISTCHECK=0 disables)"
               % ("on" if _dc.CACHE_TRACK else "off"))
        else:
            _p(f"{'site':<44s} {'hits':>8s} {'misses':>8s} "
               f"{'distinct':>9s}")
            for (kind, site), rec in stats.items():
                label = f"{kind}:{site}"[:44]
                _p(f"{label:<44s} {rec['hits']:>8d} "
                   f"{rec['misses']:>8d} {rec['distinct_keys']:>9d}")
        churn = _dc.check_churn()
        out["churn"] = [str(i) for i in churn]
        if churn:
            _p("churn findings:")
            for i in churn:
                _p(" ", i)
        else:
            _p("churn findings: none")
    except ImportError as e:
        out["distcheck_error"] = str(e)
        _p("distcheck import failed:", e)
    return out


def check_serving():
    """Serving knobs + live server state (queue depths, bucket census,
    admission rejects, tail latency) + the last drain event. Live stats
    only exist inside a serving process; the knobs and the drain record
    persist."""
    _p("---------Serving Knobs---------")
    out = {"MXNET_TPU_SERVING": os.environ.get("MXNET_TPU_SERVING")}
    _p(f"MXNET_TPU_SERVING={out['MXNET_TPU_SERVING'] or '<unset>'}  "
       "(buckets / max_queue / max_wait_ms / timeout_ms / stage — "
       "docs/SERVING.md)")
    try:
        from mxnet_tpu import serving

        out["effective"] = serving.describe()
        _p("effective     :", out["effective"])
        live = serving.live_stats()
        out["live_servers"] = live
        if not live:
            _p("live servers  : none in this process")
        for srv in live:
            _p(f"server {srv['name']!r}: started={srv['started']} "
               f"draining={srv['draining']} "
               f"uptime={srv['uptime_s']}s")
            _p(f"  {'model':<20s} {'queue':>6s} {'done':>8s} "
               f"{'rej':>6s} {'fail':>5s} {'stall':>5s} {'fill':>6s} "
               f"{'p50ms':>7s} {'p99ms':>7s}")
            for name, m in srv["models"].items():
                _p(f"  {name:<20s} {m['queue_depth']:>6d} "
                   f"{m['completed']:>8d} {m['rejected']:>6d} "
                   f"{m['failed']:>5d} {m['stalled_batches']:>5d} "
                   f"{str(m['batch_fill_ratio']):>6s} "
                   f"{str(m['p50_ms']):>7s} {str(m['p99_ms']):>7s}")
                _p(f"    bucket census: {m['bucket_census']}")
            if srv.get("last_drain"):
                _p("  last drain  :", srv["last_drain"])
        from mxnet_tpu import preempt as _preempt

        ev = _preempt.last_drain()
        out["last_drain_event"] = ev
        if ev is not None:
            _p("last drain evt:", ev.get("path"),
               f"(cause {ev.get('signal') or ev.get('reason')}, "
               f"exit {ev.get('exit_code')})")
    except ImportError as e:
        out["error"] = str(e)
        _p("serving import failed:", e)
    return out


def check_fleet():
    """Serving fleet (docs/SERVING.md "Fleet" / "Planet scale"):
    autoscaler knobs, the live fleet in this process (if any), and the
    last run's fleet.json — worker census with per-worker rps/queue/p99
    from the telemetry shards, autoscaler state + last decision, rollout
    generation history, router retry/reject counters, hedge
    counters/outcomes + straggler flags, per-host placement, and the
    QoS aggregates the merged shards carry (per-class latency, deadline
    drops/outcomes, prediction-cache census)."""
    _p("---------Serving Fleet---------")
    out = {"MXNET_TPU_FLEET": os.environ.get("MXNET_TPU_FLEET"),
           "MXTPU_FLEET_DIR": os.environ.get("MXTPU_FLEET_DIR")}
    _p(f"MXNET_TPU_FLEET={out['MXNET_TPU_FLEET'] or '<unset>'}  "
       "(min/max/up_queue/up_p99_ms/k/idle_rps/cooldown/policy/... — "
       "docs/SERVING.md 'Fleet')")
    try:
        from mxnet_tpu.serving import fleet as fleet_mod

        out["effective"] = fleet_mod.describe()
        _p("effective     :", {k: out["effective"][k] for k in
                               ("min", "max", "policy", "k",
                                "up_queue", "up_p99_ms", "idle_rps",
                                "cooldown", "interval")})
        live = [f.stats() for f in fleet_mod.live_fleets()]
        out["live_fleets"] = live
        if not live:
            _p("live fleets   : none in this process")
        run_dir = out["MXTPU_FLEET_DIR"]
        for st in live:
            _p(f"fleet {st['name']!r}: {st['state']} generation "
               f"{st['generation']}, {st['ready']}/{st['desired']} "
               f"ready @ {st.get('url')}")
            run_dir = run_dir or st.get("run_dir")
        if not run_dir:
            _p("run dir       : <none> (MXTPU_FLEET_DIR unset and no "
               "live fleet)")
            return out
        out["run_dir"] = run_dir
        try:
            with open(os.path.join(run_dir, "fleet.json")) as f:
                summary = json.load(f)
        except (OSError, ValueError) as e:
            out["summary_error"] = str(e)
            _p(f"run dir       : {run_dir} (no readable fleet.json: {e})")
            return out
        out["summary"] = summary
        _p(f"last run      : {os.path.join(run_dir, 'fleet.json')}")
        _p(f"  state       : {summary.get('state')}  generation "
           f"{summary.get('generation')}  workers "
           f"{summary.get('ready')}/{summary.get('desired')} ready  "
           f"policy {summary.get('policy')}")
        router = summary.get("router") or {}
        _p(f"  router      : {router.get('requests', 0)} requests, "
           f"{router.get('retries', 0)} retries, "
           f"{router.get('rejects', 0)} rejects, "
           f"{router.get('errors', 0)} errors")
        hedges = summary.get("hedges")
        if hedges is not None:
            rl = summary.get("router_latency") or {}
            _p(f"  hedges      : {hedges.get('fired', 0)} fired / "
               f"{hedges.get('won', 0)} won / {hedges.get('lost', 0)} "
               f"lost / {hedges.get('failed', 0)} failed  stragglers "
               f"{summary.get('stragglers')}  router p50/p99 "
               f"{rl.get('p50_ms')}/{rl.get('p99_ms')} ms")
        for h in summary.get("hosts") or []:
            _p(f"  host        : {str(h.get('name')):<10s} "
               f"{str(h.get('ssh') or 'local'):<18s} locality "
               f"{str(h.get('locality')):<7s} slots {h.get('slots')}")
        auto = summary.get("autoscaler") or {}
        last = auto.get("last_action") or auto.get("last")
        _p(f"  autoscaler  : {'on' if auto.get('enabled') else 'off'}  "
           f"decisions {auto.get('decisions')}  last "
           f"{ {k: last.get(k) for k in ('direction', 'reason', 'workers')} if last else None}")
        for r in summary.get("rollouts", []):
            _p(f"  rollout     : gen {r.get('generation')} "
               f"({r.get('state')}) <- {r.get('model_dir')} "
               f"drained {r.get('drained')}")
        _p(f"  {'slot':<5s} {'gen':>3s} {'state':<9s} {'ready':<5s} "
           f"{'rps':>8s} {'queue':>6s} {'p99ms':>8s} {'restarts':>8s} "
           f"{'host':<10s}")
        workers = summary.get("workers") or {}
        from mxnet_tpu.serving.fleet import _series_values, worker_metrics

        live_m = worker_metrics(run_dir)
        out["worker_metrics"] = live_m
        for slot, w in sorted(workers.items(), key=lambda kv: int(kv[0])):
            m = live_m.get(int(slot)) or {}
            place = str(w.get("host") or "-") \
                + (" STRAGGLER" if w.get("straggler") else "")
            _p(f"  {slot:<5s} {w.get('generation', '?'):>3} "
               f"{str(w.get('state')):<9s} {str(w.get('ready')):<5s} "
               f"{str(m.get('rps') if m.get('rps') is not None else w.get('rps')):>8s} "
               f"{str(m.get('queue_depth')):>6s} "
               f"{str(m.get('p99_ms')):>8s} "
               f"{str(w.get('restarts')):>8s} {place:<10s}")
        # QoS aggregates from the merged per-host telemetry shards:
        # per-class latency, deadline admission outcomes, cache census
        from mxnet_tpu.telemetry import fleet as tfleet

        agg = {"submit": 0.0, "queue": 0.0, "met": 0.0, "missed": 0.0,
               "hit": 0.0, "miss": 0.0}
        classes = {}
        for shard in tfleet.read_shards(run_dir).values():
            for where in ("submit", "queue"):
                agg[where] += sum(_series_values(
                    shard, "mxtpu_serving_deadline_dropped_total",
                    where=where))
            for outcome in ("met", "missed"):
                agg[outcome] += sum(_series_values(
                    shard, "mxtpu_serving_deadline_outcomes_total",
                    outcome=outcome))
            for outcome in ("hit", "miss"):
                agg[outcome] += sum(_series_values(
                    shard, "mxtpu_serving_cache_requests_total",
                    outcome=outcome))
            for klass in ("interactive", "batch"):
                for q in ("p50", "p99"):
                    vals = _series_values(
                        shard, "mxtpu_serving_class_latency_ms",
                        quantile=q, **{"class": klass})
                    if vals:
                        cur = classes.setdefault(klass, {})
                        cur[q] = max(cur.get(q, 0.0), max(vals))
        out["qos"] = {"deadline": {k: agg[k] for k in
                                   ("submit", "queue", "met", "missed")},
                      "cache_hits": agg["hit"],
                      "cache_misses": agg["miss"],
                      "by_class": classes}
        if any(agg.values()) or classes:
            _p(f"  deadlines   : dropped {int(agg['submit'])} at "
               f"submit / {int(agg['queue'])} in queue, "
               f"{int(agg['met'])} met / {int(agg['missed'])} missed")
            lookups = agg["hit"] + agg["miss"]
            _p(f"  pred. cache : {int(agg['hit'])} hits / "
               f"{int(agg['miss'])} misses"
               + (f" (hit ratio {agg['hit'] / lookups:.4f})"
                  if lookups else ""))
            for klass, cur in sorted(classes.items()):
                _p(f"  class       : {klass:<12s} p50 "
                   f"{cur.get('p50')} ms  p99 {cur.get('p99')} ms")
    except ImportError as e:
        out["error"] = str(e)
        _p("fleet import failed:", e)
    return out


def check_modelbus():
    """Model bus (docs/SERVING.md "Online updates"): the live-weight
    streaming channel between a training gang and a serving fleet —
    process totals, live watchers (applied version / staleness), and the
    bus directory's record census (versions, quarantine, rejects)."""
    _p("---------Model Bus---------")
    out = {"MXTPU_MODELBUS_DIR": os.environ.get("MXTPU_MODELBUS_DIR")}
    _p(f"MXTPU_MODELBUS_DIR={out['MXTPU_MODELBUS_DIR'] or '<unset>'}  "
       "(fleet workers subscribe when set — docs/SERVING.md "
       "'Online updates')")
    try:
        from mxnet_tpu import modelbus
    except ImportError as e:
        out["error"] = str(e)
        _p("modelbus import failed:", e)
        return out
    out["stats"] = modelbus.stats()
    _p("process totals:", out["stats"])
    watchers = [w.stats() for w in modelbus.live_watchers()]
    out["watchers"] = watchers
    if not watchers:
        _p("live watchers : none in this process")
    for w in watchers:
        _p(f"watcher {w['worker']!r}: applied v{w['applied_version']} "
           f"(step {w['applied_step']}) of latest "
           f"v{w['latest_version']} — age {w['age_steps']} steps, "
           f"{w['applied_total']} applies, rejected {w['rejected']}")
    bus_dir = out["MXTPU_MODELBUS_DIR"] or \
        (watchers[0]["bus_dir"] if watchers else None)
    if not bus_dir:
        _p("bus dir       : <none> (MXTPU_MODELBUS_DIR unset and no "
           "live watcher)")
        return out
    if not os.path.isdir(bus_dir):
        out["bus_dir_error"] = f"{bus_dir} does not exist"
        _p(f"bus dir       : {bus_dir} (does not exist)")
        return out
    desc = modelbus.ModelBus(bus_dir).describe()
    out["bus"] = desc
    _p(f"bus dir       : {bus_dir}")
    _p(f"  versions    : {desc['versions']} (latest "
       f"v{desc['latest']} @ step {desc['latest_step']}, "
       f"keep {desc['keep']})")
    _p(f"  quarantined : {desc['quarantined'] or 'none'}")
    for r in desc["rejects"]:
        _p(f"  reject      : v{r.get('version')} by "
           f"{r.get('worker')!r} — {r.get('reason')}"
           f"{': ' + r['detail'] if r.get('detail') else ''}")
    return out


def check_cluster():
    """Cluster control plane (docs/ROBUSTNESS.md "Cluster control
    plane"): the spec, the persisted world record, the desired-vs-actual
    census diff, per-role restart ledgers and the last reconcile
    actions — everything a restarted supervisor would re-adopt from."""
    import json as _json

    _p("---------Cluster---------")
    out = {"MXTPU_CLUSTER_DIR": os.environ.get("MXTPU_CLUSTER_DIR")}
    run_dir = out["MXTPU_CLUSTER_DIR"]
    _p(f"MXTPU_CLUSTER_DIR={run_dir or '<unset>'}  "
       "(world-state dir — launch.py --cluster)")
    try:
        from mxnet_tpu import cluster as _cluster
    except ImportError as e:
        out["error"] = str(e)
        _p("cluster import failed:", e)
        return out
    live = [s.describe() for s in _cluster.live_supervisors()]
    out["live_supervisors"] = live
    if live:
        for d in live:
            _p(f"live supervisor: {d['cluster']!r} incarnation "
               f"{d['incarnation']} ({d['ticks']} tick(s), "
               f"{d['adopted']} adopted)")
    else:
        _p("live supervisor: none in this process")
    if not run_dir:
        return out
    if not os.path.isdir(run_dir):
        out["run_dir_error"] = f"{run_dir} does not exist"
        _p(f"run dir       : {run_dir} (does not exist)")
        return out
    spec = None
    spec_path = os.path.join(run_dir, _cluster.SPEC_FILE)
    try:
        with open(spec_path) as f:
            spec = _json.load(f)
        out["spec"] = spec
        _p(f"spec          : {spec_path} (cluster "
           f"{spec.get('cluster')!r}, {len(spec.get('roles', {}))} "
           "role(s))")
    except (OSError, ValueError) as e:
        out["spec_error"] = str(e)
        _p(f"spec          : unreadable ({e})")
    world = _cluster.WorldState.load(run_dir)
    sup = world.supervisor or {}
    sup_alive = _cluster.pid_alive(sup.get("pid")) and \
        _cluster.proc_start_ticks(sup.get("pid")) == sup.get("start_ticks")
    out["world"] = {"incarnation": world.incarnation,
                    "torn": world.torn, "supervisor": sup,
                    "supervisor_alive": sup_alive}
    _p(f"world         : incarnation {world.incarnation}, supervisor "
       f"pid {sup.get('pid')} "
       f"({'alive' if sup_alive else sup.get('state', 'gone')})"
       f"{' [TORN — rebuilt from observation]' if world.torn else ''}")
    diff, ledgers = {}, {}
    roles = (spec or {}).get("roles", {})
    for name, slots in sorted(world.slots.items()):
        cfg = roles.get(name, {})
        desired = int(cfg.get("workers", 0) or 0)
        alive = sum(1 for rec in slots.values()
                    if rec.get("state") in ("running", "starting",
                                            "draining")
                    and _cluster.pid_alive(rec.get("pid")))
        states = {}
        for rec in slots.values():
            states[rec.get("state")] = states.get(rec.get("state"), 0) + 1
        diff[name] = {"kind": cfg.get("kind"), "desired": desired,
                      "alive": alive, "recorded": len(slots),
                      "generation": world.generation.get(name),
                      "states": states}
        ledgers[name] = world.ledger.get(name)
        drift = "" if alive == desired or cfg.get("kind") == "model-bus" \
            else f"  << drift {alive - desired:+d}"
        _p(f"  {name:<14s} {cfg.get('kind', '?'):<13s} "
           f"desired={desired} alive={alive} "
           f"gen={world.generation.get(name)} "
           f"states={states}{drift}")
    out["diff"] = diff
    out["ledgers"] = ledgers
    for name, led in sorted(ledgers.items()):
        if led and led.get("used"):
            _p(f"  ledger {name}: used={led['used']} "
               f"budget={led.get('budget')} "
               f"exhausted={led.get('exhausted')}")
    out["actions"] = world.actions[-8:]
    for a in out["actions"]:
        _p(f"  action: {a.get('kind'):<12s} {a.get('role')}"
           f"{'/s' + str(a.get('slot')) if a.get('slot') is not None else ''}"
           f" — {a.get('reason')}")
    return out


def check_watchdog():
    """Watchdog knobs + the most recent crash bundle, if one exists
    (docs/ROBUSTNESS.md) — the first thing to read after a wedged run."""
    _p("---------Watchdog Knobs--------")
    out = {"MXNET_TPU_WATCHDOG": os.environ.get("MXNET_TPU_WATCHDOG"),
           "MXNET_TPU_CRASH_DIR": os.environ.get("MXNET_TPU_CRASH_DIR")}
    _p(f"MXNET_TPU_WATCHDOG={out['MXNET_TPU_WATCHDOG'] or '<unset>'}  "
       "(hang deadlines; off unless set)")
    _p(f"MXNET_TPU_CRASH_DIR={out['MXNET_TPU_CRASH_DIR'] or '<unset>'}  "
       "(crash-bundle dir; default <tmpdir>/mxtpu_crash)")
    try:
        from mxnet_tpu import watchdog

        out["effective"] = watchdog.describe()
        _p("effective     :", out["effective"])
        bundle = watchdog.latest_bundle()
        out["latest_bundle"] = bundle
        if bundle is None:
            _p("crash bundles : none found in", watchdog.crash_dir())
            return out
        _p("latest bundle :", bundle)
        try:
            with open(os.path.join(bundle, "report.json")) as f:
                rep = json.load(f)
            out["latest_bundle_report"] = {
                "point": rep.get("point"), "label": rep.get("label"),
                "elapsed_s": rep.get("elapsed_s"),
                "deadline_s": rep.get("deadline_s"),
                "time": rep.get("time")}
            out["latest_bundle_files"] = sorted(os.listdir(bundle))
            _p("  stalled at  : %s (%s) after %.1fs (deadline %gs)"
               % (rep.get("point"), rep.get("label") or "-",
                  rep.get("elapsed_s", 0.0), rep.get("deadline_s", 0.0)))
            _p("  written     :", rep.get("time"))
            _p("  files       :", ", ".join(sorted(os.listdir(bundle))))
        except (OSError, ValueError) as e:
            out["latest_bundle_error"] = str(e)
            _p("  (report.json unreadable:", e, ")")
    except ImportError as e:
        out["error"] = str(e)
        _p("watchdog import failed:", e)
    return out


def check_preempt():
    """Preemption-drain knobs + the most recent drain event
    (docs/ROBUSTNESS.md "Preemption & elasticity") — how the last run
    ended matters for how to restart it."""
    _p("---------Preempt Knobs---------")
    out = {k: os.environ.get(k)
           for k in ("MXNET_TPU_PREEMPT", "MXNET_TPU_PREEMPT_EXIT_CODE",
                     "MXNET_TPU_PREEMPT_DIR", "MXNET_TPU_PREEMPT_RESHARD")}
    _p(f"MXNET_TPU_PREEMPT={out['MXNET_TPU_PREEMPT'] or '<unset>'}  "
       "(auto-install SIGTERM/SIGINT drain handlers; off unless set)")
    _p(f"MXNET_TPU_PREEMPT_EXIT_CODE="
       f"{out['MXNET_TPU_PREEMPT_EXIT_CODE'] or '<unset>'}  "
       "(drain exit code; default 75 = reschedule me)")
    _p(f"MXNET_TPU_PREEMPT_DIR="
       f"{out['MXNET_TPU_PREEMPT_DIR'] or '<unset>'}  "
       "(drain-event dir; default: the crash dir)")
    _p(f"MXNET_TPU_PREEMPT_RESHARD="
       f"{out['MXNET_TPU_PREEMPT_RESHARD'] or '<unset>'}  "
       "(0 forbids resuming checkpoints on a different topology)")
    try:
        from mxnet_tpu import preempt

        out["effective"] = preempt.describe()
        _p("effective     :", out["effective"])
        ev = preempt.last_drain()
        out["last_drain"] = ev
        if ev is None:
            _p("drain events  : none found in", preempt.drain_dir())
            return out
        _p("last drain    :", ev.get("path"))
        _p("  cause       :", ev.get("signal") or ev.get("reason"))
        _p("  checkpoint  :", ev.get("final_checkpoint"))
        _p("  exit code   :", ev.get("exit_code"))
    except ImportError as e:
        out["error"] = str(e)
        _p("preempt import failed:", e)
    return out


def check_gang():
    """Elastic gang supervision (docs/ROBUSTNESS.md "Gang supervision &
    elasticity"): restart-budget knobs, the last run's gang.json summary
    (generation, state, per-incarnation restart reasons), per-rank last
    heartbeats, and any post-mortem bundles left in the run dir."""
    _p("---------Gang------------------")
    out = {k: os.environ.get(k)
           for k in ("MXNET_TPU_GANG_DIR", "MXNET_TPU_GANG_MAX_RESTARTS",
                     "MXNET_TPU_GANG_BACKOFF", "MXNET_TPU_GANG_GRACE",
                     "MXNET_TPU_GANG_DEAD_S", "MXNET_TPU_GANG_SHRINK",
                     "MXTPU_GANG_DIR", "MXTPU_GANG_GENERATION")}
    _p(f"MXNET_TPU_GANG_DIR={out['MXNET_TPU_GANG_DIR'] or '<unset>'}  "
       "(shared run dir; default: a fresh tempdir per supervisor)")
    _p(f"MXNET_TPU_GANG_MAX_RESTARTS="
       f"{out['MXNET_TPU_GANG_MAX_RESTARTS'] or '<unset>'}  "
       "(restart budget; default 5, then a structured post-mortem)")
    _p(f"MXNET_TPU_GANG_BACKOFF={out['MXNET_TPU_GANG_BACKOFF'] or '<unset>'}"
       "  (first restart delay; default 1.0s, doubling to _CAP=30)")
    _p(f"MXNET_TPU_GANG_GRACE={out['MXNET_TPU_GANG_GRACE'] or '<unset>'}  "
       "(SIGTERM->SIGKILL drain deadline; default 10s)")
    _p(f"MXNET_TPU_GANG_DEAD_S={out['MXNET_TPU_GANG_DEAD_S'] or '<unset>'}  "
       "(heartbeat-silence kill threshold; default 60s, 0 disables)")
    _p(f"MXNET_TPU_GANG_SHRINK={out['MXNET_TPU_GANG_SHRINK'] or '<unset>'}  "
       "(1: killed/lost slots leave the next census — reshard smaller)")
    run_dir = out["MXTPU_GANG_DIR"] or out["MXNET_TPU_GANG_DIR"]
    try:
        from mxnet_tpu import elastic

        out["effective"] = elastic.describe()
        st = out["effective"]["stats"]
        _p(f"this process  : {st['state']} (generation "
           f"{st['generation']}, {st['restarts_total']} restart(s), "
           f"{st['postmortems']} post-mortem(s))")
        if run_dir is None:
            _p("run dir       : <none> (not in/over a supervised run)")
            return out
        summary_path = os.path.join(run_dir, "gang.json")
        try:
            with open(summary_path) as f:
                summary = json.load(f)
        except (OSError, ValueError) as e:
            out["summary_error"] = str(e)
            _p(f"run dir       : {run_dir} (no readable gang.json: {e})")
            return out
        out["summary"] = summary
        _p(f"last run      : {summary_path}")
        _p(f"  state       : {summary['state']}  generation "
           f"{summary['generation']}  restarts "
           f"{summary['restarts_used']}/{summary['max_restarts']}")
        for rec in summary.get("history", []):
            exits = ", ".join(f"r{r}={c}" for r, c in
                              sorted(rec.get("exits", {}).items()))
            _p(f"  gen {rec['generation']:<4d}: "
               f"{rec.get('reason') or 'completed'}"
               f"{'  [' + exits + ']' if exits else ''}")
        beats = elastic.read_heartbeats(run_dir)
        out["heartbeats"] = beats
        for rank in sorted(beats):
            hb = beats[rank]
            _p(f"  rank {rank} beat: {hb.get('age_s')}s ago "
               f"({hb.get('state')}, gen {hb.get('generation')}, "
               f"step {hb.get('steps')}, pid {hb.get('pid')})")
        pms = sorted(n for n in os.listdir(run_dir)
                     if n.startswith("postmortem-"))
        out["postmortems"] = pms
        if pms:
            _p(f"  post-mortem : {os.path.join(run_dir, pms[-1])}")
    except ImportError as e:
        out["error"] = str(e)
        _p("elastic import failed:", e)
    return out


def check_dataplane():
    """The streaming data plane: native library status (and, when the
    native path is off, the cached probe/build failure explaining WHY —
    the once-surfaced warning's detail), decode thread environment, and
    the host's last measured iter_bench numbers."""
    _p("---------Data Plane------------")
    out = {"cores": os.cpu_count(),
           "OMP_NUM_THREADS": os.environ.get("OMP_NUM_THREADS")}
    try:
        from mxnet_tpu import native

        st = native.status()
        out["native"] = st
        _p(f"native lib    : {'available' if st['available'] else 'OFF'} "
           f"({st['lib_path']})")
        _p(f"  capabilities: jpeg={st['jpeg']} "
           f"fused-augment={st['augment']} built={st['built']}")
        if st["error"]:
            _p(f"  why off     : {st['error']}")
        _p(f"decode threads: {out['cores']} core(s), "
           f"OMP_NUM_THREADS={out['OMP_NUM_THREADS'] or '<unset>'} "
           "(ImageRecordIter preprocess_threads bounds the OMP team)")
        shard = {"MXTPU_NUM_WORKERS":
                 os.environ.get("MXTPU_NUM_WORKERS"),
                 "MXTPU_WORKER_ID": os.environ.get("MXTPU_WORKER_ID")}
        out["shard_env"] = shard
        _p(f"reader shard  : num_parts="
           f"{shard['MXTPU_NUM_WORKERS'] or '<unset>'} part_index="
           f"{shard['MXTPU_WORKER_ID'] or '<unset>'} (gang env; "
           "explicit iterator args override)")
    except ImportError as e:
        out["error"] = str(e)
        _p("native import failed:", e)
    try:
        import tempfile

        path = os.path.join(tempfile.gettempdir(),
                            "mxtpu_iter_bench.json")
        with open(path) as f:
            last = json.load(f)
        out["last_iter_bench"] = last
        age = time.time() - last.get("time", 0)
        _p(f"last bench    : {last.get('metric')} = {last.get('value')} "
           f"{last.get('unit')} "
           f"(threads {last.get('threads')}, {age / 3600:.1f}h ago)")
        if last.get("img_s_per_core") is not None:
            _p(f"  per core    : {last['img_s_per_core']} img/s/core, "
               f"python fallback {last.get('python_img_s')} img/s, "
               f"scaling {last.get('thread_scaling')}")
        if last.get("train_data_wait_ms_mean") is not None:
            _p(f"  data_wait   : mean {last['train_data_wait_ms_mean']}"
               f"ms / max {last['train_data_wait_ms_max']}ms under the "
               "bench train loop")
    except (OSError, ValueError):
        out["last_iter_bench"] = None
        _p("last bench    : none recorded (run benchmark/iter_bench.py "
           "--augment)")
    return out


def check_telemetry():
    """Telemetry state (docs/OBSERVABILITY.md): knobs, the metrics
    registry snapshot (post-collection, the same values ``/metrics``
    serves), flight-recorder census, device-memory sample, last step
    breakdown, and tracked-executable aggregates."""
    _p("--------Telemetry--------------")
    out = {"MXNET_TPU_TELEMETRY": os.environ.get("MXNET_TPU_TELEMETRY"),
           "MXNET_TPU_FLIGHT": os.environ.get("MXNET_TPU_FLIGHT")}
    _p(f"MXNET_TPU_TELEMETRY={out['MXNET_TPU_TELEMETRY'] or '<unset>'}  "
       "(push instrumentation; on unless 0)")
    _p(f"MXNET_TPU_FLIGHT={out['MXNET_TPU_FLIGHT'] or '<unset>'}  "
       "(flight-recorder ring size; default 1024, 0 disables)")
    try:
        from mxnet_tpu import telemetry

        desc = telemetry.describe()
        out["effective"] = desc
        _p("effective     :", {k: desc[k] for k in
                               ("enabled", "flight_ring", "flight_events",
                                "memory_sample_every")})
        snap = telemetry.metrics_snapshot()
        out["metrics"] = snap
        _p(f"metrics       : {len(snap)} registered series families "
           "(full values in --json / GET /metrics)")
        from mxnet_tpu.telemetry import flight, memory, steps

        tail = flight.tail(5)
        out["flight_tail"] = tail
        _p(f"flight        : {sum(flight.counts().values())} events "
           f"({dict(flight.counts())})")
        for ev in tail:
            _p(f"  {ev['kind']:<16s} {ev['point']:<16s} "
               f"{str(ev['label'] or '')[:40]}")
        mem = memory.device_memory()
        out["device_memory"] = mem
        for r in mem:
            _p(f"memory        : {r['device']} live={r['live_bytes']} "
               f"peak={r['peak_bytes']} ({r['source']})")
        last = steps.last()
        out["last_step"] = last
        if last:
            _p(f"last step     : #{last['step']} "
               f"{last['duration_ms']}ms phases={last['phases']}"
               + (f" mfu_xla={last['mfu_xla']}"
                  if last.get("mfu_xla") is not None else ""))
        from mxnet_tpu.telemetry import memory as _mem

        top = _mem.top_executables(5)
        out["top_executables"] = top
        for r in top:
            _p(f"resident exe  : [{r['site']}] {r['resident_bytes']} B "
               f"(temp {r['temp_bytes']}, out {r['output_bytes']})")
    except ImportError as e:
        out["error"] = str(e)
        _p("telemetry import failed:", e)
    return out


def check_tracing():
    """Span tracing + fleet aggregation (docs/OBSERVABILITY.md
    "Tracing"): ring knob, committed-span census, the last merged-trace
    dump, per-rank telemetry shard ages in the gang run dir, and the
    current straggler verdict."""
    _p("---------Tracing---------------")
    out = {"MXNET_TPU_TRACE": os.environ.get("MXNET_TPU_TRACE"),
           "MXNET_TPU_STRAGGLER_FACTOR":
               os.environ.get("MXNET_TPU_STRAGGLER_FACTOR"),
           "MXNET_TPU_STRAGGLER_PERSIST":
               os.environ.get("MXNET_TPU_STRAGGLER_PERSIST")}
    _p(f"MXNET_TPU_TRACE={out['MXNET_TPU_TRACE'] or '<unset>'}  "
       "(span-ring size; default 2048, 0 disables tracing)")
    _p(f"MXNET_TPU_STRAGGLER_FACTOR="
       f"{out['MXNET_TPU_STRAGGLER_FACTOR'] or '<unset>'}  "
       "(slowest-rank score threshold; default 1.5)")
    _p(f"MXNET_TPU_STRAGGLER_PERSIST="
       f"{out['MXNET_TPU_STRAGGLER_PERSIST'] or '<unset>'}  "
       "(consecutive flagged steps before 'persistent'; default 3)")
    try:
        from mxnet_tpu.telemetry import fleet, trace

        desc = trace.describe()
        out["effective"] = desc
        _p(f"span ring     : {desc['ring']} "
           f"({'on' if desc['enabled'] else 'OFF'}), "
           f"{desc['retained']} retained")
        _p(f"span counts   : {desc['spans'] or '(none committed)'}")
        out["last_merged_trace"] = desc["last_dump"]
        _p("last trace    :",
           desc["last_dump"]
           or "(none dumped — run tools/traceview.py)")
        fdesc = fleet.describe()
        out["fleet"] = fdesc
        run_dir = fdesc["installed_dir"] \
            or os.environ.get("MXTPU_GANG_DIR") \
            or os.environ.get("MXNET_TPU_GANG_DIR")
        out["run_dir"] = run_dir
        if run_dir:
            ages = fleet.shard_ages(run_dir)
            out["shard_ages"] = ages
            if ages:
                for rank in sorted(ages):
                    _p(f"rank {rank} shard  : {ages[rank]}s old")
            else:
                _p(f"rank shards   : none readable in {run_dir}")
        else:
            _p("rank shards   : <no gang run dir>")
        v = fdesc["verdict"]
        out["straggler"] = v
        if v is None:
            _p("straggler     : no verdict computed in this process")
        elif v.get("status") != "ok":
            _p(f"straggler     : {v.get('status')} "
               f"(ranks {v.get('ranks')})")
        else:
            who = v["slowest_rank"]
            _p(f"straggler     : "
               f"{'rank %s' % who if who is not None else 'none'} "
               f"(score {v['score']}, skew {v['skew_ms']}ms, "
               f"{'PERSISTENT' if v['persistent'] else 'streak %d' % v['streak']}"
               f" @ step {v['last_common_step']})")
    except ImportError as e:
        out["error"] = str(e)
        _p("telemetry import failed:", e)
    return out


def check_gradcomms():
    """Gradient comms (docs/PERFORMANCE.md): the bucketed async
    cross-host reduction pipeline — knobs, bucket plan sizes, fusion
    counts, overlap ratio, pending-future depth."""
    _p("-------Gradient Comms----------")
    out = {"MXNET_TPU_BUCKET_BYTES":
           os.environ.get("MXNET_TPU_BUCKET_BYTES"),
           "MXNET_TPU_BUCKET_FORCE":
           os.environ.get("MXNET_TPU_BUCKET_FORCE"),
           "MXNET_TPU_GRAD_SCATTER":
           os.environ.get("MXNET_TPU_GRAD_SCATTER")}
    try:
        from mxnet_tpu.kvstore import buckets

        out["cap_bytes"] = buckets.bucket_bytes()
        _p(f"bucket cap    : {out['cap_bytes']} bytes "
           f"(MXNET_TPU_BUCKET_BYTES="
           f"{out['MXNET_TPU_BUCKET_BYTES'] or '<unset>'}; 0 = legacy "
           "per-key collectives)")
        _p(f"trainer knobs : MXNET_TPU_GRAD_SCATTER="
           f"{out['MXNET_TPU_GRAD_SCATTER'] or '<unset>'} (dp grad "
           "reduce-scatter pin)")
        cs = buckets.comm_stats()
        out["stats"] = cs
        _p(f"fused         : {cs['fused']} collectives over "
           f"{cs['keys']} key payloads, {cs['bytes']} bytes "
           f"({cs['partial']} partial, {cs['drains']} forced drains)")
        _p(f"overlap       : ratio {cs['overlap_ratio']} (blocked "
           f"{cs['wait_ms']}ms of {cs['window_ms']}ms in flight); "
           f"pending futures {cs['pending']} "
           f"(max {cs['max_pending']})")
        cen = buckets.census()
        out["pipelines"] = cen
        if not cen:
            _p("pipelines     : none live (no dist kvstore constructed, "
               "or bucketing disabled)")
        for p in cen:
            plan = p["plan"]
            sizes = [b["bytes"] for b in plan["buckets"]]
            _p(f"pipeline      : {plan['keys']} keys in "
               f"{len(plan['buckets'])} buckets, bytes {sizes[:8]}"
               f"{'...' if len(sizes) > 8 else ''}; "
               f"pending {p['pending']['inflight']}")
    except ImportError as e:
        out["error"] = str(e)
        _p("kvstore import failed:", e)
    return out


def check_kernels():
    """Pallas kernel layer (docs/PERFORMANCE.md "Pallas kernel layer"):
    registry census, dispatch-table location/entries/staleness, per-
    family dispatch win/loss + fallback latches, and the last
    ``opperf --kernels`` autotune run — everything needed to answer
    "which op families actually run their Pallas kernel here, and did
    anything fall back silently?"."""
    _p("---------Kernels----------")
    out = {}
    try:
        from mxnet_tpu import kernels as klayer

        fams = klayer.families()
        out["families"] = fams
        out["enabled"] = klayer.enabled()
        out["pallas_available"] = klayer.pallas_available()
        out["on_tpu"] = klayer.on_tpu()
        gate = "" if klayer.enabled() else "  [MXNET_TPU_KERNELS=0 — " \
            "every family forced to XLA]"
        _p(f"registry      : {len(fams)} families "
           f"({', '.join(fams)}){gate}")
        _p(f"pallas        : "
           f"{'available' if out['pallas_available'] else 'UNAVAILABLE'}"
           f", backend={'tpu' if out['on_tpu'] else 'non-tpu'}")

        census = klayer.table.census()
        out["table"] = census
        if census["path"] is None:
            _p("dispatch table: memory-only (no MXNET_TPU_CACHE_DIR)")
        else:
            state = "present" if census["exists"] else "ABSENT"
            _p(f"dispatch table: {census['path']} [{state}] "
               f"fp={census['fingerprint']} backend={census['backend']}")
        w = census["winners"]
        _p(f"  entries     : {census['entries']} "
           f"(kernel wins {w.get('kernel', 0)}, "
           f"xla wins {w.get('xla', 0)})")
        for fam, rec in sorted(census["per_family"].items()):
            _p(f"    {fam:<20s} kernel={rec.get('kernel', 0)} "
               f"xla={rec.get('xla', 0)}")
        if census["corrupt_seen"]:
            _p(f"  corrupt     : {census['corrupt_seen']}")
        op = census["opperf"]
        if op is None:
            _p("  autotune    : never run for this fingerprint "
               "(benchmark/opperf.py --kernels)")
        else:
            import datetime as _dt

            when = _dt.datetime.fromtimestamp(
                op["when"]).strftime("%Y-%m-%d %H:%M:%S")
            _p(f"  autotune    : {when} ({op.get('cases')} cases, "
               f"{op.get('duration_s')}s, "
               f"interpret={op.get('interpret')})")

        stats = klayer.dispatch_stats()
        out["dispatch_stats"] = stats
        out["fallback"] = klayer.fallback_report()
        if not stats:
            _p("dispatches    : none this process")
        for fam, rec in stats.items():
            reasons = ", ".join(f"{k}={v}" for k, v in
                                sorted(rec["reasons"].items()))
            _p(f"  {fam:<20s} kernel={rec['kernel']} xla={rec['xla']} "
               f"({reasons})")
        warned = out["fallback"]["warned_families"]
        if warned:
            _p(f"latched       : {', '.join(warned)} (Pallas "
               f"unavailable — warned once, counting in "
               f"mxtpu_kernels_fallback_total)")

        from mxnet_tpu.telemetry import registry as _treg

        snap = {}
        for metric in ("mxtpu_kernels_dispatch_total",
                       "mxtpu_kernels_fallback_total",
                       "mxtpu_kernels_table_corrupt_total"):
            m = _treg.get(metric)
            if m is not None:
                vals = {",".join(k) or "total": v
                        for k, v in m.series().items()}
                if vals:
                    snap[metric] = vals
        out["counters"] = snap
        for metric, vals in snap.items():
            _p(f"  {metric}: {vals}")
    except ImportError as e:
        out["error"] = str(e)
        _p("kernels import failed:", e)
    return out


def check_quantization():
    """Int8 quantization state (docs/PERFORMANCE.md "Int8 inference"):
    the last calibration run in this process (mode / histogram bins /
    per-tensor thresholds), the last graph-pass census (per-channel vs
    per-tensor vs embedding weights), the live int8 serving ladders
    (weight_dtype + bucket census) and the serving compile site's
    disk-cache warmth — everything needed to answer "is this process
    actually serving the calibrated int8 model, warm?"."""
    _p("---------Quantization----------")
    out = {}
    try:
        from mxnet_tpu.contrib import quantization as quant

        calib = quant.last_calibration()
        out["last_calibration"] = calib
        if calib is None:
            _p("calibration   : none run in this process")
        else:
            _p(f"calibration   : mode={calib['mode']} "
               f"bins={calib['num_bins']} examples={calib['examples']} "
               f"({calib['batches']} batches)")
            for tname, rec in sorted(calib["tensors"].items()):
                if "threshold" in rec:
                    _p(f"  {tname:<28s} th={rec['threshold']:g} "
                       f"kl={rec['kl_divergence']:g} seen="
                       f"[{rec['min_seen']:g}, {rec['max_seen']:g}] "
                       f"bins={rec['bins']}")
                else:
                    _p(f"  {tname:<28s} range=[{rec.get('min')}, "
                       f"{rec.get('max')}]")
        census = quant.last_quantization()
        out["last_pass"] = census
        if census is None:
            _p("graph pass    : none run in this process")
        else:
            _p(f"graph pass    : {census['granularity']} — "
               f"{census['per_channel']} per-channel + "
               f"{census['per_tensor']} per-tensor weights; ops "
               f"{census['ops']}")
        from mxnet_tpu import serving

        int8_models = {}
        for srv in serving.live_stats():
            for name, m in srv.get("models", {}).items():
                if m.get("weight_dtype") == "int8":
                    int8_models[name] = {
                        "buckets": m.get("buckets"),
                        "bucket_census": m.get("bucket_census"),
                        "completed": m.get("completed")}
        out["live_int8_models"] = int8_models
        if not int8_models:
            _p("int8 serving  : no live int8 models in this process")
        for name, m in int8_models.items():
            _p(f"int8 model    : {name} ladder={m['buckets']} "
               f"census={m['bucket_census']} completed={m['completed']}")
        from mxnet_tpu import compile as _compile

        sstats = _compile.stats().get("serving")
        out["serving_compile"] = sstats
        if sstats:
            _p(f"serving site  : hits={sstats.get('hits')} "
               f"misses={sstats.get('misses')} "
               f"disk_hits={sstats.get('disk_hits')} (disk hits = the "
               "ladder warmed from the persistent cache)")
    except ImportError as e:
        out["error"] = str(e)
        _p("quantization import failed:", e)
    return out


SECTIONS = (
    ("python", check_python),
    ("pip", check_pip),
    ("framework", check_framework),
    ("dependencies", check_deps),
    ("hardware", check_hardware),
    ("environment", check_environment),
    ("analysis", check_analysis),
    ("concurrency", check_concur),
    ("compile_cache", check_compile_cache),
    ("serving", check_serving),
    ("serving_fleet", check_fleet),
    ("model_bus", check_modelbus),
    ("cluster", check_cluster),
    ("kernels", check_kernels),
    ("quantization", check_quantization),
    ("watchdog", check_watchdog),
    ("preempt", check_preempt),
    ("gang", check_gang),
    ("dataplane", check_dataplane),
    ("grad_comms", check_gradcomms),
    ("telemetry", check_telemetry),
    ("tracing", check_tracing),
)


def collect(gc=False, echo=True):
    """Run every section; returns the full report dict. ``echo=False``
    collects silently (the --json path)."""
    global _ECHO
    prev, _ECHO = _ECHO, echo
    report = {}
    try:
        for name, fn in SECTIONS:
            try:
                report[name] = fn(gc=gc) if name == "compile_cache" \
                    else fn()
            except Exception as e:  # one broken probe must not kill the rest
                report[name] = {"error": f"{type(e).__name__}: {e}"}
                _p(f"{name} check failed:", e)
    finally:
        _ECHO = prev
    return report


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        prog="diagnose", description="mxnet_tpu environment report")
    ap.add_argument("--gc", action="store_true",
                    help="prune stale-fingerprint / corrupt entries from "
                         "the on-disk compile cache (MXNET_TPU_CACHE_DIR)")
    ap.add_argument("--json", action="store_true",
                    help="emit the whole report as one JSON document "
                         "(CI scraping) instead of human text")
    args = ap.parse_args(argv if argv is not None else [])
    report = collect(gc=args.gc, echo=not args.json)
    if args.json:
        print(json.dumps(report, sort_keys=True, default=repr))


if __name__ == "__main__":
    import sys as _sys

    main(_sys.argv[1:])
