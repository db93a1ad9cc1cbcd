"""The comparison that decides ``correct``: the system's logits against
the configuration's plain float32 ``reference`` on the same weights and
inputs, as max |difference| over max |reference|, held to the tolerance
the configuration's file gives with its reason."""
import numpy as np


def against_reference(bench, net, x, got):
    """``(ok, note)`` for the system's answer ``got`` to inputs ``x``
    under the weights ``net`` holds now."""
    import jax

    tol = float(bench.cfg["check"]["tolerance"])
    params = bench.model.export_params(net, bench.cfg)
    ref = jax.jit(lambda p, xx: bench.model.reference(
        bench.cfg, p, (xx, None))["logits"])
    want = np.asarray(ref(params, x)).astype(np.float32)
    got = np.asarray(got).astype(np.float32)
    scale = float(np.abs(want).max())
    err = float(np.abs(got - want).max()) / max(scale, 1e-30) \
        if got.shape == want.shape else float("inf")
    ok = bool(np.isfinite(got).all() and err <= tol)
    return ok, {"samples": int(len(x)), "max_err_over_scale": err,
                "tolerance": tol, "output_scale": scale}
