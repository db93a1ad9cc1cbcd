"""Pipeline parallelism (pp axis): GPipe-style microbatch pipelining.

Beyond the reference: MXNet 1.x only offers manual `group2ctx` placement
for model parallelism; this module provides real pipeline scheduling the
TPU way — no per-stage processes, no send/recv framework. The whole
pipeline is ONE jitted SPMD program: each device on the ``pp`` mesh axis
holds one stage's parameters (stacked pytree, leading dim = stages),
activations flow stage-to-stage with `lax.ppermute` over ICI, and the
skewed schedule is a `lax.scan` over M + S - 1 ticks (M microbatches
through S stages — the GPipe fill/drain schedule). The program is fully
differentiable, so `jax.grad` through it yields pipeline-parallel
BACKWARD for free (XLA reverses the ppermutes).

Constraint (standard for SPMD pipelining): every stage must have the same
input/output shape and the same parameter structure — the "stack of
identical blocks" regime of transformer LMs. Embed/head layers live
outside the pipelined region.

    stages_params = stack_stage_params([blk.collect_params() ...])
    fn = pipeline_apply(stage_fn, mesh, num_microbatches=8)
    y = fn(stages_params, x)   # == sequential application of all stages
"""
from __future__ import annotations

__all__ = ["pipeline_apply", "stack_stage_params"]


def _check_stacked_leading_dim(stacked_params, n, what):
    """Trace-time validation: every leaf's leading dim must equal the
    mesh-axis size (a 2n-stage stack would silently use every other
    slice via p[0]). Raises (not assert — `-O` must not strip it)."""
    import jax

    leaves = jax.tree_util.tree_leaves(stacked_params)
    if not leaves:
        raise ValueError(f"stacked {what} params are empty")
    lead = {p.shape[0] for p in leaves}
    if lead != {n}:
        raise ValueError(
            f"stacked {what} params have leading dims {sorted(lead)}; "
            f"the {what} axis has {n} devices")


def stack_stage_params(param_trees):
    """Stack S identical-structure parameter pytrees along a new leading
    axis (the pp-sharded dimension)."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *param_trees)


def pipeline_apply(stage_fn, mesh, num_microbatches, axis="pp"):
    """Build the pipelined callable.

    Parameters
    ----------
    stage_fn : (params_slice, x) -> y with ``y.shape == x.shape``; one
        stage's computation as a pure function.
    mesh : DeviceMesh with a ``pp`` (or `axis`) dimension.
    num_microbatches : microbatches the global batch is split into; must
        divide the batch size. More microbatches = smaller pipeline
        bubble (bubble fraction = (S-1)/(M+S-1)).

    Returns
    -------
    fn(stacked_params, x) -> y — jit-compiled SPMD program. x is the
    FULL batch (B, ...); stacked_params has leading dim S (sharded over
    the pp axis).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    jmesh = mesh.jax_mesh
    num_stages = mesh.size(axis)
    m = num_microbatches

    def local(params, xs):
        # params: this stage's slice, leading dim 1 -> squeeze
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
        # mark the carries as device-varying over pp (shard_map's vma check
        # rejects a scan whose carry changes variance mid-loop)
        pcast = jax.lax.pcast
        state = pcast(jnp.zeros_like(xs[0]), axis, to="varying")
        out_buf = pcast(jnp.zeros_like(xs), axis, to="varying")

        def tick(carry, t):
            state, out_buf = carry
            # stage 0 ingests microbatch t (while it exists); other stages
            # consume the activation ppermuted in from the previous stage
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, m - 1), 0, keepdims=False)
            inp = jnp.where(stage == 0, feed, state)
            y = stage_fn(params, inp)
            # last stage banks microbatch t-(S-1) when it is in range
            out_idx = t - (num_stages - 1)
            write = (stage == num_stages - 1) & (out_idx >= 0)
            updated = jax.lax.dynamic_update_index_in_dim(
                out_buf, y, jnp.clip(out_idx, 0, m - 1), 0)
            out_buf = jnp.where(write, updated, out_buf)
            state = jax.lax.ppermute(y, axis, perm)
            return (state, out_buf), None

        (state, out_buf), _ = jax.lax.scan(
            tick, (state, out_buf), jnp.arange(m + num_stages - 1))
        # results live on the last stage; replicate them across pp
        out_buf = jnp.where(stage == num_stages - 1, out_buf,
                            jnp.zeros_like(out_buf))
        return jax.lax.psum(out_buf, axis)

    sharded = jax.shard_map(local, mesh=jmesh,
                        in_specs=(P(axis), P()), out_specs=P())

    @jax.jit
    def run(stacked_params, x):
        _check_stacked_leading_dim(stacked_params, num_stages, "pp")
        b = x.shape[0]
        if b % m:
            raise ValueError(
                f"batch {b} not divisible by microbatches {m}")
        xs = x.reshape((m, b // m) + x.shape[1:])
        out = sharded(stacked_params, xs)
        return out.reshape((b,) + out.shape[2:])

    return run
