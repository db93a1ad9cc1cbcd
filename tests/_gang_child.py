"""Elastic gang worker for tests/test_elastic.py and chaos_smoke phase 8.

One rank of a supervised gang (``tools/launch.py --supervise``): a tiny
deterministic ShardedTrainer fit over GLOBAL steps, checkpointing through a
CheckpointManager shared across the gang, draining gracefully on SIGTERM
(the supervisor's coordinated teardown) and ALWAYS resuming from the
manager's latest good checkpoint — so a generation-N+1 incarnation picks up
exactly where the drained generation stopped, resharding onto the surviving
census when the mesh shrank.

Census -> mesh: each rank simulates ``GC_BASE_DEVICES x MXTPU_NUM_WORKERS``
local CPU devices (or the explicit ``GC_DEVICES`` override for solo
reference runs), so a gang that shrank from 2 workers to 1 resumes on half
the devices — a genuine topology-portable reshard. Ranks train the SAME
data-parallel trajectory (the mesh is process-local: multiprocess CPU
collectives are not available on every jax in CI; the TCP rendezvous layer
itself is unit-tested through base.maybe_init_distributed), and only rank 0
writes checkpoints/outputs.

Env knobs (GC_* are this child's; MXTPU_* come from the supervisor):

    GC_CKPT_DIR       checkpoint dir (default: <MXTPU_GANG_DIR>/ckpt)
    GC_TOTAL          total global steps (default 12)
    GC_EPOCH          steps per epoch -> checkpoint cadence (default 4)
    GC_BASE_DEVICES   simulated devices per worker (default 2)
    GC_DEVICES        explicit device count override (reference runs)
    GC_STEP_SLEEP     seconds slept per step (default 0 — drills set ~0.2
                      so a mid-epoch kill lands mid-epoch, not after done)
    GC_OUT            rank 0: np.savez final params + per-step losses +
                      __start__ (resume step) + __generation__/__devices__
    GC_FAULTS_GEN1    fault spec armed ONLY by rank 0 in generation 1
                      (e.g. "trainer.step:peerloss@6:1" — kill rank 1 at
                      step 6); later generations run clean, so the drill
                      converges instead of re-killing every incarnation
    GC_STRAGGLE_RANK  this rank arms a per-step delay fault
                      (trainer.step:delay@*) — the deterministic
                      straggler for the PR 12 skew-detection drills
    GC_STRAGGLE_MS    the straggler's per-step delay (default 200)
    GC_METRICS        "1": start a per-rank telemetry MetricsServer,
                      advertise its port in the rank's telemetry shard,
                      and before exiting (a) scrape the OWN endpoint
                      into <gang dir>/rank-scrape-<r>.txt — the
                      fleet-sum acceptance compares the fleet scrape
                      against these — and (b) write one final shard
    GC_SERVE          "1": rank 0 serves a tiny model for a few traced
                      requests after training (request spans with all
                      five phases land in its shard for the merged
                      gang trace)
"""
import os
import sys

# device census must land before anything touches the XLA backend
_workers = int(os.environ.get("MXTPU_NUM_WORKERS", "1") or 1)
_n = int(os.environ.get("GC_DEVICES", "0") or 0) or \
    int(os.environ.get("GC_BASE_DEVICES", "2")) * _workers
# the gang mesh here is process-local (see module docstring): drop the
# rendezvous address so jax.distributed does not try to form a global
# device pool this jax/backend cannot serve
os.environ.pop("MXTPU_COORDINATOR", None)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", _n)

import time  # noqa: E402

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import faults, gluon, preempt  # noqa: E402
from mxnet_tpu.checkpoint import CheckpointManager  # noqa: E402
from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer  # noqa: E402


def batch_for(epoch, step):
    rs = np.random.RandomState(1000 * epoch + step)
    x = rs.randn(8, 6).astype(np.float32)
    y = (x @ rs.randn(6, 4) * 0.5).astype(np.float32)
    return mx.nd.array(x), mx.nd.array(y)


def main():
    total = int(os.environ.get("GC_TOTAL", "12"))
    per_epoch = int(os.environ.get("GC_EPOCH", "4"))
    sleep_s = float(os.environ.get("GC_STEP_SLEEP", "0") or 0)
    rank = int(os.environ.get("MXTPU_WORKER_ID", "0") or 0)
    generation = int(os.environ.get("MXTPU_GANG_GENERATION", "1") or 1)
    gang_dir = os.environ.get("MXTPU_GANG_DIR")
    ckpt_dir = os.environ.get("GC_CKPT_DIR") or (
        os.path.join(gang_dir, "ckpt") if gang_dir else None)
    if ckpt_dir is None:
        raise SystemExit("GC_CKPT_DIR or MXTPU_GANG_DIR is required")
    out = os.environ.get("GC_OUT") if rank == 0 else None

    preempt.install()
    spec = os.environ.get("GC_FAULTS_GEN1")
    if spec and rank == 0 and generation == 1:
        faults.configure(spec)
    straggle = os.environ.get("GC_STRAGGLE_RANK")
    if straggle is not None and rank == int(straggle):
        delay_s = float(os.environ.get("GC_STRAGGLE_MS", "200")) / 1e3
        faults.configure(f"trainer.step:delay@*:{delay_s}")
    metrics_server = None
    if os.environ.get("GC_METRICS"):
        from mxnet_tpu.telemetry import fleet
        from mxnet_tpu.telemetry.export import MetricsServer

        metrics_server = MetricsServer(port=0).start()
        fleet.set_shard_info(metrics_port=metrics_server.port)

    mx.random.seed(7)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"))
    net.add(gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(batch_for(1, 0)[0])
    trainer = ShardedTrainer(net, gluon.loss.L2Loss(), "adam",
                             {"learning_rate": 0.05},
                             mesh=DeviceMesh({"dp": jax.device_count()}))
    manager = CheckpointManager(ckpt_dir, prefix="gang", keep=5)

    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # the reshard notice on a shrink
        entry = trainer.resume(manager)
    start = entry["step"] if entry is not None else 0

    losses = []
    for g in range(start, total):
        epoch, s = divmod(g, per_epoch)
        x, y = batch_for(epoch + 1, s)
        losses.append(float(trainer.step(x, y).asscalar()))
        if sleep_s:
            time.sleep(sleep_s)
        if rank == 0 and (g + 1) % per_epoch == 0:
            trainer.save_checkpoint(manager, (g + 1) // per_epoch)
        if preempt.requested():
            # rank 0's last-resort hook writes the final checkpoint; the
            # others must not race it in the shared manager
            preempt.drain(save=None if rank == 0 else False,
                          directory=ckpt_dir)  # SystemExit(75)

    if os.environ.get("GC_SERVE") and rank == 0:
        # a few traced requests so the gang trace carries serving
        # request spans (five phases) alongside the step spans
        from mxnet_tpu import serving

        snet = gluon.nn.Dense(4, in_units=6)
        snet.initialize(mx.init.Xavier())
        snet(mx.nd.zeros((2, 6)))
        cont = serving.ModelContainer()
        cont.add_block("gangserve", snet, example_shape=(6,),
                       buckets=(2,))
        srv = serving.ModelServer(cont, max_wait_ms=1.0).start()
        srv.warmup()
        for i in range(4):
            srv.predict("gangserve",
                        np.zeros((1, 6), np.float32), timeout=10.0)
        srv.drain(timeout=10.0)
        srv.stop()

    if metrics_server is not None and gang_dir:
        # freeze this rank's story: scrape the own endpoint (the
        # per-rank truth the fleet sums are checked against), then
        # write a final telemetry shard carrying the same counters
        import urllib.request

        from mxnet_tpu.telemetry import fleet

        text = urllib.request.urlopen(
            metrics_server.url + "/metrics", timeout=10).read().decode()
        with open(os.path.join(gang_dir, f"rank-scrape-{rank}.txt"),
                  "w") as f:
            f.write(text)
        fleet.write_shard(gang_dir, rank, generation)

    if out:
        np.savez(out, __losses__=np.asarray(losses, np.float64),
                 __start__=np.int64(start),
                 __generation__=np.int64(generation),
                 __devices__=np.int64(jax.device_count()),
                 **{name: p.data().asnumpy()
                    for name, p in net.collect_params().items()})
    print(f"GANG_DONE rank={rank} generation={generation} start={start} "
          f"t={trainer._t} devices={jax.device_count()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
