"""Expert parallelism (ep axis): mixture-of-experts layer.

Beyond the reference (MXNet 1.x has no MoE): experts are partitioned
across the ``ep`` mesh axis — each device owns one expert's parameters
(stacked pytree, leading dim = experts) — inside ONE jitted SPMD program.
Top-1 routing follows the Switch-Transformer recipe: a linear router
scores tokens, each token goes to its argmax expert, the expert output is
scaled by the router probability (keeps routing differentiable), and a
load-balancing auxiliary loss penalizes expert collapse.

Combine strategy: each device computes its expert on the full token set
masked to its assignment, and a `psum` over ep merges the disjoint
results — the dense-dispatch formulation, which on TPU is one all-reduce
over ICI and no host-side gather/scatter. (All-to-all token dispatch is a
bandwidth optimization of the same math for when experts dominate
compute.)

    fn = moe_apply(expert_fn, mesh)
    y, aux_loss = fn(stacked_expert_params, router_w, x)
"""
from __future__ import annotations

__all__ = ["moe_apply", "stack_expert_params"]

from .pipeline import _check_stacked_leading_dim
from .pipeline import stack_stage_params as stack_expert_params


def moe_apply(expert_fn, mesh, axis="ep"):
    """Build the expert-parallel MoE callable.

    Parameters
    ----------
    expert_fn : (params_slice, x) -> y — one expert, same output shape.
    mesh : DeviceMesh with an ``ep`` axis; its size = number of experts.

    Returns
    -------
    fn(stacked_params, router_w, x) -> (y, aux_loss) where x is (N, d),
    router_w is (d, E), y is (N, d_out); aux_loss is the Switch
    load-balancing term (scalar, add it to the training loss).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    jmesh = mesh.jax_mesh
    num_experts = mesh.size(axis)

    def local(params, router_w, x):
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        e = jax.lax.axis_index(axis)
        logits = x @ router_w                       # (N, E) replicated
        probs = jax.nn.softmax(logits, axis=-1)
        assigned = jnp.argmax(probs, axis=-1)       # (N,)
        mine = (assigned == e)                      # (N,) this device's tokens
        gate = jnp.where(mine, jnp.max(probs, axis=-1), 0.0)  # (N,)
        y = expert_fn(params, x)                    # (N, d_out)
        y = y * gate[:, None]
        y = jax.lax.psum(y, axis)                   # disjoint merge
        # Switch aux loss: E * sum_e fraction_e * mean_prob_e — each device
        # contributes its own expert's f_e * P_e term, summed over ep
        frac_e = jnp.mean(mine.astype(jnp.float32))
        mean_p_e = jnp.mean(probs, axis=0)[e]
        aux = num_experts * jax.lax.psum(frac_e * mean_p_e, axis)
        return y, aux

    sharded = jax.shard_map(local, mesh=jmesh,
                        in_specs=(P(axis), P(), P()),
                        out_specs=(P(), P()))

    @jax.jit
    def run(stacked_params, router_w, x):
        _check_stacked_leading_dim(stacked_params, num_experts, "ep")
        if router_w.shape[-1] != num_experts:
            # silently-dropped experts otherwise: tokens routed past
            # column E match no device and psum to zero rows
            raise ValueError(
                f"router_w has {router_w.shape[-1]} expert columns but "
                f"the ep axis has {num_experts} devices")
        y, aux = sharded(stacked_params, router_w, x)
        return y, jnp.reshape(aux, ())

    return run
