"""Mode ``train``: a steady training job through ``ShardedTrainer``.

Traffic keys: ``global_batch``, ``mesh`` (e.g. ``{"dp": 4}``),
``pool_batches`` (seeded batches made on the device and cycled),
``warmup_steps``, ``trace_steps`` (steps profiled in a ``--trace 1`` run),
``trainer_options`` (keyword arguments of ``ShardedTrainer`` beyond its
defaults; ``{}`` is what users get), and whatever the configuration's
``make_batch`` reads (``seq_len``). The optimizer is the configuration's
``job``.

Every step ends in a blocking read of its loss, as the default
``nan_guard`` does anyway. The window is ``--seconds`` long and closes on
the read of the last step begun inside it. ``train_samples_per_s`` is the
global batch over the MEDIAN wall time of a step (start of the
``trainer.step`` call to the end of the blocking read) over the window's
steps: a one-chip machine shares its host's CPU cores, and a stall of a
tenth of a second that has nothing to do with the program moved the mean
of a 5 s window by 2 % and the median not at all (PERF.md, PR 22). The
mean over the window (steps x batch / seconds) is on the ``train`` line.
"""
import time

import numpy as np

from chipbench.harness import check, stats
from chipbench.harness.bench import Outcome


def _sharded_pool(bench, mesh):
    """``pool_batches`` seeded batches, made in one jitted call and laid
    out over the mesh the way ``ShardedTrainer._put_batch`` would lay
    them (so its own placement is a no-op)."""
    import jax

    n = int(bench.traffic["pool_batches"])

    def make(key):
        return [bench.model.make_batch(bench.cfg, bench.traffic, k)
                for k in jax.random.split(key, n)]

    shapes = jax.eval_shape(make, jax.random.PRNGKey(0))
    shardings = [(mesh.sharding(*(("dp",) + (None,) * (len(x.shape) - 1))),
                  mesh.sharding("dp")) for x, _ in shapes]
    key = jax.random.fold_in(jax.random.PRNGKey(bench.seed), 0xDA7A)
    return jax.jit(make, out_shardings=shardings)(key)


def _step(bench, trainer, batch, record):
    x, y = batch
    t0 = time.perf_counter()
    with bench.span("train.step_call"):
        loss = trainer.step(x, y)
    t1 = time.perf_counter()
    with bench.span("train.loss_read"):
        value = float(loss.asscalar())
    record.append((t0, t1, time.perf_counter(), value))
    return loss


def _check_logits(bench, trainer, net, dp):
    """The network in inference mode against the reference, on seeded
    inputs and seeded weights, outside the window. The weights the window
    left are overwritten: against them the comparison is ill-conditioned
    (``check.reason`` in the configuration's file)."""
    import jax.numpy as jnp

    bench.model.seed_params(net, bench.cfg, bench.seed)
    n = max(int(bench.cfg["check"]["samples"]), dp)
    x = bench.model.check_inputs(bench.cfg, bench.seed, n)
    # float inputs enter in the network's type, integer ids as they are
    x_sys = x.astype(jnp.dtype(bench.cfg["dtype"])) \
        if np.issubdtype(x.dtype, np.floating) else x
    return check.against_reference(
        bench, net, x, trainer.predict(x_sys).asnumpy())


def run(bench):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import kernels
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer
    from mxnet_tpu.telemetry import steps as tsteps

    traffic, job = bench.traffic, bench.cfg["job"]
    mesh = DeviceMesh(dict(traffic["mesh"]))
    kernels.reset_stats()
    net = bench.model.build(bench.cfg, mx.tpu(), bench.seed)
    mx.random.seed(bench.seed)
    trainer = ShardedTrainer(
        net, bench.model.loss(bench.cfg), job["optimizer"],
        dict(job["optimizer_params"]), mesh=mesh,
        **traffic.get("trainer_options", {}))
    pool = _sharded_pool(bench, mesh)

    warm = []
    for i in range(int(traffic["warmup_steps"])):
        _step(bench, trainer, pool[i % len(pool)], warm)
    bench.setup_done()

    # ----------------------------------------------------------- window --
    tsteps.reset()
    rec, stretches, i = [], [[]], 0
    t_close = time.perf_counter() + bench.seconds
    # a --trace 1 run profiles a few tens of steps a third of the way in;
    # the untraced stretches before and after it give the rate for mfu
    trace_at = t_close - bench.seconds * 2.0 / 3.0 if bench.trace else None
    loss = None
    while time.perf_counter() < t_close:
        if trace_at is not None and time.perf_counter() >= trace_at:
            bench.trace_start()
            for _ in range(int(traffic["trace_steps"])):
                loss = _step(bench, trainer, pool[i % len(pool)], rec)
                i += 1
            bench.trace_stop()
            trace_at = None
            stretches.append([])
            continue
        loss = _step(bench, trainer, pool[i % len(pool)], rec)
        i += 1
        stretches[-1].append(rec[-1])
    bench.window_closed()

    # ---------------------------------------------------- after the window
    gb = int(traffic["global_batch"])
    untraced = [r for s in stretches for r in s]
    n_steps = len(untraced)
    secs = sum(s[-1][2] - s[0][0] for s in stretches if s)
    step_s = stats.median([r[2] - r[0] for r in untraced])
    samples_per_s = gb / step_s if step_s else 0.0

    losses = [r[3] for r in warm + rec]
    cycle = len(pool)
    head, tail = losses[:cycle], losses[-cycle:]
    params = net.collect_params()
    first_w = next(iter(params.values())).data()._data
    spans_devices = len(first_w.sharding.device_set)
    platforms = {d.platform for d in first_w.devices()}
    logits_ok, logits_note = _check_logits(bench, trainer, net,
                                           mesh.size("dp"))
    checks = {
        "losses_finite": bool(np.isfinite(losses).all()),
        "skipped_steps": trainer.skipped_steps,
        "loss_first_cycle": float(np.mean(head)),
        "loss_last_cycle": float(np.mean(tail)),
        "parameters_span_devices": spans_devices,
        "loss_spans_devices": len(loss._data.sharding.device_set),
        "platforms": sorted(platforms),
        "logits": logits_note,
    }
    correct = bool(
        checks["losses_finite"] and trainer.skipped_steps == 0
        and checks["loss_last_cycle"] < checks["loss_first_cycle"]
        and spans_devices == mesh.num_devices
        and checks["loss_spans_devices"] == mesh.num_devices
        and platforms == {jax.devices()[0].platform} and logits_ok)

    run_bag = {
        "mode": "train", "workload": bench.name, "traffic": traffic,
        "cfg": bench.cfg, "model": bench.model,
        "chips": mesh.num_devices, "global_batch": gb,
        "samples_per_s": samples_per_s,
        "step_records": rec,
        "step_history": tsteps.history(),
        "dispatch_stats": kernels.dispatch_stats(),
    }
    notes = {
        "train": {"steps_in_window": len(rec), "rate_steps": n_steps,
                  "rate_seconds": secs, "global_batch": gb,
                  "samples_per_s": samples_per_s,
                  "mean_samples_per_s": gb * n_steps / secs if secs else 0.0,
                  "step_ms_p10_p50_p90": [stats.percentile(
                      [(r[2] - r[0]) * 1e3 for r in rec], q)
                      for q in (10, 50, 90)],
                  "phase_ms_median": {
                      ph: stats.median([h["phases"].get(ph, 0.0)
                                        for h in run_bag["step_history"]])
                      for ph in ("h2d", "compute", "sync", "other")},
                  "losses": [round(v, 4) for v in
                             losses[:4] + losses[-4:]]},
        "checks": checks,
        "dispatch": run_bag["dispatch_stats"],
    }
    return Outcome(correct=correct, attempted=len(rec), failed=0,
                   end_to_end={"train_samples_per_s": samples_per_s},
                   run=run_bag, notes=notes)
