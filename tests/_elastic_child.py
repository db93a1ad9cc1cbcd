"""Elastic preemption child for tests/test_elastic.py.

Runs a tiny deterministic ShardedTrainer fit over GLOBAL steps (so a
mid-epoch drain can resume at the exact batch), checkpointing through
CheckpointManager at epoch boundaries and draining gracefully on SIGTERM.
Driven entirely by env vars so the parent test can run every variant of
the SAME trajectory:

    EL_CKPT_DIR   checkpoint directory (shared between drain + resume runs)
    EL_TOTAL      total global steps (default 12)
    EL_EPOCH      steps per epoch (default 4)
    EL_DEVICES    simulated device count — applied BEFORE the jax backend
                  initialises (jax_num_cpu_devices, exactly like
                  tests/conftest.py)
    EL_RESUME     "1" -> resume from the manager's latest good checkpoint
    EL_RESHARD    "0" -> forbid cross-topology resume (reshard=False)
    EL_OUT        where to np.savez the final params + per-step losses
    MXNET_TPU_FAULTS  e.g. "trainer.step:preempt@6" — SIGTERM to self at
                      step 6; the preempt handlers drain: step 6 finishes,
                      a final checkpoint lands, exit code 75

The per-(epoch, step) batches are regenerated from a derived seed, so a
resumed run replays the identical data stream from `entry["step"]` — the
data-position half of the drain/resume contract.
"""
import os
import sys

# device count must land before anything touches the XLA backend
_n = int(os.environ.get("EL_DEVICES", "0"))
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
if _n:
    jax.config.update("jax_num_cpu_devices", _n)

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import gluon, preempt  # noqa: E402
from mxnet_tpu.checkpoint import CheckpointManager  # noqa: E402
from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer  # noqa: E402


def batch_for(epoch, step):
    rs = np.random.RandomState(1000 * epoch + step)
    x = rs.randn(8, 6).astype(np.float32)
    y = (x @ rs.randn(6, 4) * 0.5).astype(np.float32)
    return mx.nd.array(x), mx.nd.array(y)


def main():
    total = int(os.environ.get("EL_TOTAL", "12"))
    per_epoch = int(os.environ.get("EL_EPOCH", "4"))
    ckpt_dir = os.environ["EL_CKPT_DIR"]
    out = os.environ.get("EL_OUT")

    preempt.install()
    mx.random.seed(7)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"))
    net.add(gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(batch_for(1, 0)[0])
    trainer = ShardedTrainer(net, gluon.loss.L2Loss(), "adam",
                             {"learning_rate": 0.05},
                             mesh=DeviceMesh({"dp": jax.device_count()}))
    manager = CheckpointManager(ckpt_dir, prefix="el", keep=5)

    start = 0
    if os.environ.get("EL_RESUME") == "1":
        reshard = None if os.environ.get("EL_RESHARD") != "0" else False
        entry = trainer.resume(manager, reshard=reshard)
        if entry is not None:
            start = entry["step"]  # exact data position, mid-epoch included

    losses = []
    for g in range(start, total):
        epoch, s = divmod(g, per_epoch)
        x, y = batch_for(epoch + 1, s)
        losses.append(float(trainer.step(x, y).asscalar()))
        if (g + 1) % per_epoch == 0:
            trainer.save_checkpoint(manager, (g + 1) // per_epoch)
        if preempt.requested():
            preempt.drain(directory=ckpt_dir)  # final ckpt + SystemExit(75)

    if out:
        np.savez(out, __losses__=np.asarray(losses, np.float64),
                 **{name: p.data().asnumpy()
                    for name, p in net.collect_params().items()})
    print(f"EL_DONE t={trainer._t} devices={jax.device_count()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
