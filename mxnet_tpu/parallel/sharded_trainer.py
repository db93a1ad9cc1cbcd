"""ShardedTrainer: the whole training step as ONE sharded XLA executable.

Replaces, in a single compiled computation laid out over a DeviceMesh, what
the reference spreads across per-GPU executors + kvstore:

  forward (DataParallelExecutorGroup.forward, executor_group.py:445)
  backward (:581)
  gradient allreduce (kvstore 'device': comm.h:503 Reduce + :598 Broadcast)
  optimizer update (fused update ops, optimizer_op.cc:49-970)
  BatchNorm running-stat writeback (aux state)

Gradients of replicated parameters computed from dp-sharded batches come out
of XLA as all-reduces over ICI; tp-sharded parameters get their activations
partitioned by GSPMD. Parameter/optimizer buffers are donated, so the update
is in-place at the XLA level (no 2x parameter memory).
"""
from __future__ import annotations

import itertools
import operator
import weakref
from typing import Dict, List, Optional

import numpy as _np

from .. import autograd
from ..cached_op import TraceScope
from ..ndarray import NDArray
from .mesh import DeviceMesh

__all__ = ["ShardedTrainer", "sharding_rules"]


def sharding_rules(params, mesh: DeviceMesh) -> Dict[str, tuple]:
    """Default per-parameter PartitionSpecs (the group2ctx analogue).

    Everything is replicated except, when the mesh has a tp axis > 1,
    matmul/conv weights whose output dim divides tp — those are split on the
    output dimension (Megatron column parallel); GSPMD propagates the rest.
    """
    tp = mesh.size("tp")
    rules: Dict[str, tuple] = {}
    for name, p in params.items():
        shape = p.shape
        spec: tuple = ()
        if tp > 1 and shape and len(shape) >= 2 and shape[0] % tp == 0 \
                and name.endswith("weight"):
            spec = ("tp",) + (None,) * (len(shape) - 1)
        rules[name] = spec
    return rules


class ShardedTrainer:
    """Compiled data/tensor-parallel trainer over a DeviceMesh.

    Parameters
    ----------
    net : HybridBlock with materialized parameters.
    loss_fn : callable (pred NDArray, label NDArray) -> loss NDArray
        (e.g. a gluon loss block).
    optimizer : any registered optimizer name (the full 17-entry zoo:
        sgd/nag/signum/lars/lbsgd/sgld/dcasgd/adam/ftml/lamb/adagrad/
        rmsprop/adadelta/ftrl/adamax/nadam/test) or an Optimizer
        instance; the update math runs INSIDE the compiled step via
        opt_rules.py, reusing the ops/optimizer_op.py kernels.
        multi_precision=True keeps fp32 master weights for bf16 params.
    mesh : DeviceMesh (default: all devices on dp)
    rules : optional {param_name: PartitionSpec tuple} overriding defaults.
    """

    def __init__(self, net, loss_fn, optimizer="sgd", optimizer_params=None,
                 mesh: Optional[DeviceMesh] = None, rules=None, donate=True,
                 zero=False, remat=False, accum_steps=1, nan_guard=True,
                 max_consecutive_skips=8):
        """Extra memory levers (all off by default, numerics unchanged):

        zero : ZeRO-1 — optimizer state lives dp-sharded (state memory
            divided by the dp size) and the update math runs sharded;
            only the parameter delta is all-gathered. Expressed as GSPMD
            sharding constraints, not manual collectives.
        remat : `jax.checkpoint` around the forward — backward
            recomputes activations instead of storing them (long-context
            / deep-model memory for FLOPs trade).
        accum_steps : gradient accumulation — the global batch is split
            into this many microbatches scanned inside the ONE compiled
            step (activation memory of one microbatch, numerics of the
            full batch for deterministic nets; stochastic layers like
            Dropout draw one rng key per microbatch, so their sample
            stream differs from the accum=1 run).

        Robustness levers:

        nan_guard : a non-finite loss or gradient SKIPS the whole update
            (optimizer state, aux and every parameter without a master
            are selected back to their pre-step values INSIDE the
            compiled step — one jnp.where per buffer, no extra
            transfers; a low-precision parameter under multi_precision
            is the cast of its SELECTED fp32 master and has no select of
            its own, which on a skipped step is the old parameter bit
            for bit and keeps its update one pass over the state: a
            select with the old parameter as an operand is one XLA
            splits from the state's fusion and feeds by recomputing the
            rule, 44 bytes a parameter under Adam for 28), so one bad
            batch cannot poison the run. Skips are counted (``skipped_steps`` /
            ``consecutive_skips``, and in the profiler when recording);
            after `max_consecutive_skips` skips in a row step() raises —
            a permanently diverged run must fail loudly, not spin.
            Reading the skip flag synchronizes the host with each step's
            completion, so the device has nothing queued from the end of
            one step to the enqueue of the next. step() therefore does
            before the enqueue only what the launch needs, after the read
            only what needs the flag, and everything else (the rng
            stream's advance, rebinding the outputs, the next step's
            signature, letting go of the donated inputs) between the
            two, while the device runs. nan_guard=False runs the same
            order without the read: fully async dispatch, for when that
            latency matters more than the guard.
        """
        self._net = net
        self._loss_fn = loss_fn
        self._mesh = mesh or DeviceMesh()
        self._multiprocess = self._mesh.is_multiprocess
        self._donate = donate
        self._zero = bool(zero)
        self._remat = bool(remat)
        self._accum = int(accum_steps)
        if self._accum < 1:
            raise ValueError("accum_steps must be >= 1")
        self._nan_guard = bool(nan_guard)
        self._max_consecutive_skips = int(max_consecutive_skips)
        # multi-host dp gradient overlap: pin each gradient to a
        # dp-sharded layout (the ZeRO state layout) so XLA materializes
        # the cross-host grad sum as reduce-scatter + all-gather — which
        # the latency-hiding scheduler can overlap with backward — not
        # one monolithic all-reduce at the end of backward. Numerics
        # match up to XLA reduction order. MXNET_TPU_GRAD_SCATTER=0
        # opts out; ZeRO already implies the same layout.
        import os as _os

        self._grad_scatter = (
            self._multiprocess and self._mesh.size("dp") > 1
            and _os.environ.get("MXNET_TPU_GRAD_SCATTER", "1") != "0")
        self.skipped_steps = 0       # total updates skipped by the guard
        self.consecutive_skips = 0   # current skip streak
        opt_params = dict(optimizer_params or {})
        # lr_scheduler makes the learning rate a TRACED scalar argument
        # of the compiled step (one executable, lr varies per call)
        self._lr_scheduler = opt_params.pop("lr_scheduler", None)
        self._lr = float(opt_params.pop("learning_rate", 0.01))
        # the eager optimizer instance validates hyper-params and is the
        # static hyper source for the compiled update rule (opt_rules.py)
        from .. import optimizer as _opt_mod
        from .opt_rules import RULES

        if isinstance(optimizer, _opt_mod.Optimizer):
            self._opt = optimizer
            if opt_params:
                # hypers live on the instance; silently ignoring leftovers
                # would train with different dynamics than requested
                raise ValueError(
                    "optimizer_params other than learning_rate/"
                    "lr_scheduler cannot be combined with an Optimizer "
                    f"instance: {sorted(opt_params)}")
            # honour the instance's own lr/scheduler unless explicitly
            # overridden through optimizer_params
            if "learning_rate" not in (optimizer_params or {}):
                self._lr = float(self._opt.lr)
            if self._lr_scheduler is None and \
                    self._opt.lr_scheduler is not None:
                self._lr_scheduler = self._opt.lr_scheduler
        else:
            try:
                self._opt = _opt_mod.create(
                    optimizer, learning_rate=self._lr, **opt_params)
            except TypeError as e:
                raise ValueError(
                    f"unsupported optimizer params for {optimizer!r}: "
                    f"{e}") from None
        if self._lr_scheduler is not None:
            # same contract as Optimizer: learning_rate seeds the
            # scheduler's base_lr (optimizer/optimizer.py:41) — AFTER the
            # instance branch may have adopted the instance's lr
            self._lr_scheduler.base_lr = self._lr
        self._opt_name = type(self._opt).__name__.lower()
        if self._opt_name not in RULES:
            raise ValueError(
                f"no compiled update rule for optimizer "
                f"{self._opt_name!r}; available: {sorted(RULES)}")
        self._rule = RULES[self._opt_name]
        if self._opt_name == "lbsgd" and self._opt.batch_scale > 1 \
                and self._accum == 1:
            import warnings

            warnings.warn(
                "LBSGD batch_scale>1: the compiled step applies the "
                "large-batch lr warmup every step but does NOT "
                "accumulate gradients — pass accum_steps (or feed the "
                "full macro-batch) for the accumulation half",
                stacklevel=2)
        self._wd = float(self._opt.wd)

        params = net.collect_params()
        self._param_names = []
        self._train_handles: List[NDArray] = []
        self._aux_names = []
        self._aux_handles: List[NDArray] = []
        for name, p in params.items():
            if p._data is None:
                raise ValueError(
                    f"Parameter {name!r} not initialized; run one forward "
                    "pass (or initialize with explicit shapes) first")
            if p.grad_req != "null":
                self._param_names.append(name)
                self._train_handles.append(p.data())
            else:
                self._aux_names.append(name)
                self._aux_handles.append(p.data())
        self._rules = dict(sharding_rules(params, self._mesh))
        if rules:
            self._rules.update(rules)
        # distributed-correctness pre-check (analysis.distcheck pass 1):
        # a rule naming an absent axis would otherwise SILENTLY replicate
        # in _place_params below — fail here, param-named, with
        # did-you-mean hints (MXNET_TPU_DISTCHECK=0 opts out)
        from ..analysis import distcheck as _distcheck

        self._distcheck = _distcheck.enabled()
        if self._distcheck:
            names = self._param_names + self._aux_names
            handles = self._train_handles + self._aux_handles
            check_rules = {n: self._rules.get(n, ()) for n in names}
            for n, spec in self._rules.items():
                # user rules naming no parameter are dead — keep them in
                # the checked set so the typo gets a did-you-mean hint
                check_rules.setdefault(n, spec)
            _distcheck.run(
                rules=check_rules,
                shapes={n: tuple(h.shape)
                        for n, h in zip(names, handles)},
                mesh=self._mesh, churn=False)
        self._wd_mult = [1.0 if (n.endswith("weight") or n.endswith("gamma"))
                         else 0.0 for n in self._param_names]
        # parameters with an fp32 master (multi_precision, low-precision
        # weight), and weak references to the arrays their handles held
        # when the trainer last bound them (_pair_masters)
        self._mastered = tuple(
            i for i, h in enumerate(self._train_handles)
            if getattr(self._opt, "multi_precision", False)
            and str(h._data.dtype) in ("bfloat16", "float16"))
        self._paired = ()
        self._opt_raws = self._init_opt_state()
        self._step_fn = None
        # the signature nodes of the last step's outputs, built while the
        # device ran it (_remember_signature): (weak references to the
        # leaves, state slots per parameter, nodes)
        self._sig_memo = None
        self._t = 0
        # elasticity plumbing: the manager/epoch of the newest checkpoint,
        # so a preemption drain (or watchdog abort) can write a final one
        self._ckpt_manager = None
        self._ckpt_epoch = 0
        # model-bus publishing (publish_to): armed, every K-th successful
        # step streams a versioned weight record into the bus directory
        self._bus = None
        self._bus_every = 1
        self._bus_rollback = True
        self._bus_model = None
        self._bus_topk = None
        self.published_versions = []
        self._place_params()
        # one env var (MXNET_TPU_PREEMPT) arms graceful SIGTERM drains
        from .. import preempt as _preempt

        _preempt.maybe_install_from_env()

    # ------------------------------------------------------------ set-up ---

    def _global_put(self, host_arr, sh):
        """Multi-host-safe placement under a prebuilt NamedSharding."""
        return self._mesh.global_put(host_arr, sharding=sh)

    def _put_batch(self, raw, sh):
        """Lay a data batch out under `sh`. Multi-host: the caller passes
        its PROCESS-LOCAL portion of the global batch (the standard SPMD
        data-loading contract — each worker loads its own slice); the
        global batch is the concatenation over processes."""
        import jax

        if not self._multiprocess:
            return jax.device_put(raw, sh)
        if sh.is_fully_replicated:
            # per-rank slices would become INCONSISTENT replicas of one
            # "global" array and silently drift the hosts apart
            raise ValueError(
                "multi-host batch placement needs a process-spanning "
                "batch ('dp') axis in the mesh; this mesh replicates "
                "the batch — add a dp axis, or feed every process the "
                "identical batch via jax.device_put yourself")
        return jax.make_array_from_process_local_data(
            sh, _np.asarray(jax.device_get(raw)))

    @property
    def learning_rate(self):
        """Current (scheduled) lr — parity: optimizer.py learning_rate
        property, which consults the scheduler at the current step."""
        if self._lr_scheduler is not None:
            return float(self._lr_scheduler(self._t))
        return self._lr

    @learning_rate.setter
    def learning_rate(self, lr):
        self.set_learning_rate(lr)

    def set_learning_rate(self, lr):
        """Change the lr mid-training (gluon Trainer parity, including
        the UserWarning raised when a scheduler already drives the lr —
        optimizer.py set_learning_rate). The lr is a traced argument of
        the compiled step, so no recompilation."""
        if self._lr_scheduler is not None:
            raise UserWarning("LRScheduler of the optimizer has already "
                              "been defined.")
        self._lr = float(lr)

    def _spec_for(self, name):
        return self._mesh.sharding(*self._rules.get(name, ()))

    def _dp_sharded_full(self, spec, shape):
        """`spec` additionally dp-sharded on the first divisible
        unsharded dim (no divisible dim: unchanged, the constraint is a
        no-op) — the ZeRO-1 state layout AND the grad reduce-scatter
        layout."""
        dp = self._mesh.size("dp")
        full = spec + (None,) * (len(shape) - len(spec))
        if dp > 1 and "dp" not in full:
            for i, (s, d) in enumerate(zip(full, shape)):
                if s is None and d % dp == 0:
                    full = full[:i] + ("dp",) + full[i + 1:]
                    break
        return full

    def _state_spec_for(self, name, shape):
        """Optimizer-state layout: the parameter's own spec, or — under
        ZeRO — additionally dp-sharded on the first divisible unsharded
        dim, dividing state memory by the dp size (ZeRO-1)."""
        # trim to the state's own rank: scalar states (e.g. Nadam's
        # momentum schedule) of a tp-sharded weight stay replicated
        spec = tuple(self._rules.get(name, ()))[:len(shape)]
        if not self._zero:
            return self._mesh.sharding(*spec)
        return self._mesh.sharding(*self._dp_sharded_full(spec, shape))

    def _grad_spec_for(self, name, shape):
        """Gradient reduce-scatter layout (``_grad_scatter``): dp-shard
        the gradient like ZeRO shards state, so the cross-host grad sum
        lowers to reduce-scatter + all-gather instead of one blocking
        all-reduce."""
        spec = tuple(self._rules.get(name, ()))[:len(shape)]
        return self._mesh.sharding(*self._dp_sharded_full(spec, shape))

    def _place_params(self):
        """Lay parameters out on the mesh per the rules (replicate or
        tp-shard) — the device_put that replaces per-GPU weight copies.
        Multi-host meshes go through _global_put (each process
        contributes its addressable shards of the same full copy)."""
        for name, h in zip(self._param_names, self._train_handles):
            h._rebind(self._global_put(h._data, self._spec_for(name)))
        for name, h in zip(self._aux_names, self._aux_handles):
            h._rebind(self._global_put(h._data, self._mesh.replicated()))
        self._opt_raws = tuple(
            tuple(self._global_put(s, self._state_spec_for(name, s.shape))
                  for s in per)
            for name, per in zip(self._param_names, self._opt_raws))
        self._pair_masters()

    def _pair_masters(self):
        """Remember the arrays the mastered parameters' handles hold now
        as the ones their masters belong to. The compiled step derives
        such a parameter as cast(master) and never reads the parameter
        for its update, so the pair has to hold whenever a step starts:
        every place where the trainer itself binds both calls this
        (placement, a step's commit, load_states, unshard), and
        _adopt_outside_writes finds what anything else bound. Weak
        references, as in _remember_signature."""
        self._paired = tuple(weakref.ref(self._train_handles[i]._data)
                             for i in self._mastered)

    def _adopt_outside_writes(self):
        """A mastered parameter whose handle no longer holds the array
        the trainer bound was written from outside (``set_data``,
        ``load_parameters``, ``x[:] = ...`` on ``param.data()``): the
        written value is the weight now, so its master is re-derived
        from it, as ``_init_opt_state`` derived the first one; the
        rule's own state (momentum, Adam's moments) stays. Without this
        the next step would compute from the old master and the write
        would be lost."""
        opt = None
        for i, ref in zip(self._mastered, self._paired):
            w = self._train_handles[i]._data
            if ref() is w:
                continue
            opt = list(self._opt_raws) if opt is None else opt
            # through the host, as a loaded checkpoint's master goes: a
            # rare event, and whole on a process-spanning mesh too
            w32 = self._global_put(
                _np.asarray(self._host_copy(w)).astype(_np.float32),
                self._state_spec_for(self._param_names[i], w.shape))
            opt[i] = (w32,) + tuple(opt[i][1:])
        if opt is not None:
            self._opt_raws = tuple(opt)
            self._pair_masters()

    def _init_opt_state(self):
        """Per-parameter state from the rule's factory. Under
        multi-precision an fp32 master copy is PREPENDED to each low-
        precision parameter's state and the rule's own state is built in
        fp32 (parity: create_state_multi_precision)."""
        import jax.numpy as jnp

        out = []
        for i, h in enumerate(self._train_handles):
            w = h._data
            if i in self._mastered:
                w32 = jnp.asarray(w, jnp.float32)
                out.append((w32,) + self._rule.init(self._opt, w32))
            else:
                out.append(self._rule.init(self._opt, w))
        return tuple(out)

    # ------------------------------------------------------------- build ---
    def _service_token(self, kind):
        """Process-stable identity of the compiled step for the unified
        compile service (mxnet_tpu.compile): everything the trace BAKES
        into the executable that the aval signature cannot see — network
        structure (gluon repr), loss, optimizer rule + scalar hypers, wd
        schedule, sharding rules and the memory/robustness levers."""
        import hashlib

        hypers = tuple(sorted(
            (k, v) for k, v in vars(self._opt).items()
            if isinstance(v, (int, float, bool, str, type(None)))))
        from .. import kernels as _kernels

        blob = "\n".join([
            repr(self._net), repr(self._loss_fn), self._opt_name,
            repr(hypers), repr(self._wd), repr(self._wd_mult),
            repr(tuple(self._param_names)), repr(tuple(self._aux_names)),
            repr(sorted(self._rules.items())),
            repr(self._mesh.describe()),
            repr((self._donate, self._zero, self._remat, self._accum,
                  self._nan_guard, self._grad_scatter)),
            # what step_fn does with its arguments beyond their avals:
            # it takes split(rng)[1] itself (an executable persisted by a
            # build whose caller split must not be loaded by this one)
            "rng=split-in-step",
            # ... and derives a mastered parameter as the cast of its
            # selected master (an executable whose step selected the
            # parameter beside the state computes the same values in two
            # passes: it must not be loaded in this one's place)
            "update=cast-of-selected-master",
            # kernel-dispatch identity: a retuned table or a flipped
            # MXNET_TPU_KERNELS must not reuse an executable traced
            # under the old routing
            _kernels.token_salt()])
        return ("trainer", kind,
                hashlib.sha1(blob.encode()).hexdigest()[:16])

    def warmup(self, x, y):
        """AOT warmup: build + compile the step executable for batches
        shaped like ``x``/``y`` (NDArray, jax array, or
        ``jax.ShapeDtypeStruct``) WITHOUT running a step — the pod
        cold-start hook. Registering the step with the compile service
        also replays any pending warmup-manifest entries recorded by a
        previous run, so every previously-seen batch signature compiles
        (or disk-loads) here rather than at first traffic."""
        x_raw = x._data if isinstance(x, NDArray) else x
        y_raw = y._data if isinstance(y, NDArray) else y
        if self._step_fn is None:
            if self._distcheck:
                # same pre-compile sharding surface check step() runs
                from ..analysis import distcheck as _dc

                _dc.check_trainer(self, x_raw, y_raw)
            self._step_fn = self._build(x_raw, y_raw)
        from .. import compile as _compile

        return _compile.warmup()

    def aot_lower(self, x, y):
        """AOT-lower the full train step under GSPMD for batches shaped
        like ``x``/``y`` WITHOUT executing it (and without consuming the
        RNG stream) — the compile-cleanliness proof for a training
        config before hardware is available (``__graft_entry__``'s
        multichip dryrun lowers the flagship dp×tp+ZeRO+remat config
        through this). Returns the jax ``Lowered``; ``.compile()``
        finishes the XLA pipeline and its HLO text feeds
        ``analysis.distcheck.schedule_from_hlo`` for the collective
        census."""
        import jax

        from .. import random as _rand

        x_raw = x._data if isinstance(x, NDArray) else x
        y_raw = y._data if isinstance(y, NDArray) else y
        if self._step_fn is None:
            if self._distcheck:
                from ..analysis import distcheck as _dc

                _dc.check_trainer(self, x_raw, y_raw)
            self._step_fn = self._build(x_raw, y_raw)
        key = _rand.current_key()  # aval only; the stream does not advance

        def aval(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        import jax.numpy as jnp

        return self._step_fn.lower(
            tuple(aval(h._data) for h in self._train_handles),
            tuple(tuple(aval(s) for s in per) for per in self._opt_raws),
            tuple(aval(h._data) for h in self._aux_handles),
            jax.ShapeDtypeStruct(tuple(x_raw.shape),
                                 _np.dtype(x_raw.dtype)),
            jax.ShapeDtypeStruct(tuple(y_raw.shape),
                                 _np.dtype(y_raw.dtype)),
            aval(key), jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.float32))

    def _build(self, x_raw, y_raw):
        import jax
        import jax.numpy as jnp

        net = self._net
        loss_fn = self._loss_fn
        train_handles = self._train_handles
        aux_handles = self._aux_handles
        wd = self._wd
        wd_mult = self._wd_mult
        opt = self._opt
        rule = self._rule
        mastered = self._mastered
        n_aux = len(aux_handles)

        def run_net(praws, araws, x, y, rng):
            saved = [(h, h._data) for h in train_handles + aux_handles]
            scope = TraceScope(rng)
            try:
                for h, r in zip(train_handles, praws):
                    h._data = r
                for h, r in zip(aux_handles, araws):
                    h._data = r
                with scope, autograd.pause(train_mode=True):
                    out = net.forward(NDArray(x))
                    loss = loss_fn(out, NDArray(y)).mean()
                updates = {id(h): raw for h, raw in scope.state_updates}
                new_aux = tuple(updates.get(id(h), r)
                                for h, r in zip(aux_handles, araws))
                return loss._data, new_aux
            finally:
                for h, orig in saved:
                    h._data = orig

        if self._remat:
            # trade FLOPs for memory: backward re-derives activations
            run_net = jax.checkpoint(run_net)
        accum = self._accum
        zero = self._zero
        # ZeRO-1: the state layout each param's update math is pinned to
        state_sh = [self._state_spec_for(n, h._data.shape)
                    for n, h in zip(self._param_names, train_handles)]

        def grads_of(praws, araws, x, y, rng):
            """(loss, new_aux), grads for the FULL batch — directly, or
            accumulated over `accum` scanned microbatches (activation
            memory of one microbatch, numerics of the whole batch)."""
            if accum == 1:
                return jax.value_and_grad(run_net, has_aux=True)(
                    praws, araws, x, y, rng)
            b = x.shape[0]
            if b % accum:
                raise ValueError(
                    f"batch {b} not divisible by accum_steps {accum}")
            dp = self._mesh.size("dp")
            if (b // accum) % dp:
                import warnings

                warnings.warn(
                    f"microbatch size {b // accum} not divisible by the "
                    f"dp size {dp}: some devices idle every scan step — "
                    "accumulation should trade memory for time, not "
                    "parallelism", stacklevel=3)
            xs = x.reshape((accum, b // accum) + x.shape[1:])
            ys = y.reshape((accum, b // accum) + y.shape[1:])
            # keep each microbatch dp-sharded after the fold
            xs = jax.lax.with_sharding_constraint(
                xs, self._mesh.sharding(
                    *((None, "dp") + (None,) * (len(x.shape) - 1))))
            rngs = jax.random.split(rng, accum)

            def micro(carry, inp):
                g_acc, loss_acc, araws_c = carry
                xm, ym, rm = inp
                (l, new_aux), g = jax.value_and_grad(
                    run_net, has_aux=True)(praws, araws_c, xm, ym, rm)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (g_acc, loss_acc + l, new_aux), None

            init = (jax.tree_util.tree_map(jnp.zeros_like, praws),
                    jnp.zeros((), jnp.float32), araws)
            (g_sum, loss_sum, new_aux), _ = jax.lax.scan(
                micro, init, (xs, ys, rngs))
            grads = jax.tree_util.tree_map(lambda g: g / accum, g_sum)
            return (loss_sum / accum, new_aux), grads

        nan_guard = self._nan_guard
        grad_scatter = self._grad_scatter
        grad_sh = [self._grad_spec_for(n, h._data.shape)
                   for n, h in zip(self._param_names, train_handles)] \
            if grad_scatter else None

        def step_fn(praws, opt_raws, araws, x, y, rng, t, lr):
            # `rng` is the global stream's key as the caller found it; the
            # step's own is the draw next_key() would have made of it
            # (the caller advances the stream once this is enqueued)
            rng = jax.random.split(rng)[1]
            (loss, new_aux), grads = grads_of(praws, araws, x, y, rng)
            if nan_guard:
                # one fused all-finite reduction over loss + every grad;
                # the flag also gates the select-back below
                with jax.named_scope(GUARD_SCOPE):
                    finite = jnp.isfinite(loss)
                    for g in grads:
                        finite = jnp.logical_and(finite,
                                                 jnp.all(jnp.isfinite(g)))
            else:
                finite = jnp.bool_(True)
            tt = t.astype(jnp.float32)

            def keep(new, old):
                # NaN/Inf step guard: select a buffer back to its pre-step
                # value when any grad (or the loss) is non-finite — the
                # update is skipped entirely, on device
                return jnp.where(finite, new, old) if nan_guard else new

            new_p, new_opt = [], []
            with jax.named_scope(UPDATE_SCOPE):
                for i, (w, g, st) in enumerate(zip(praws, grads, opt_raws)):
                    pwd = wd * wd_mult[i]
                    if zero:
                        # pin gradient (and hence the state and delta math) to
                        # the dp-sharded state layout; XLA all-gathers only
                        # the final parameter delta (ZeRO-1)
                        g = jax.lax.with_sharding_constraint(g, state_sh[i])
                    elif grad_scatter:
                        # multi-host dp: the same dp-sharded pin on the grad
                        # alone — the cross-host sum becomes reduce-scatter
                        # (+ all-gather of the delta), overlappable with
                        # backward by the latency-hiding scheduler
                        g = jax.lax.with_sharding_constraint(g, grad_sh[i])
                    rng_i = jax.random.fold_in(rng, i + 1)  # stochastic rules
                    if i in mastered:
                        # fp32 master copy leads the state tuple; the rule
                        # runs entirely in fp32
                        w32n, innern = rule.update(
                            opt, st[0], g.astype(jnp.float32), st[1:], lr, pwd,
                            tt, rng_i)
                        stn = tuple(keep(ns, s) for ns, s in
                                    zip((w32n,) + tuple(innern), st))
                        # the parameter is the cast of the SELECTED master and
                        # has no select of its own: the old parameter is
                        # cast(old master) (_adopt_outside_writes keeps the
                        # pair so), and a select with an operand the fp32
                        # ones lack is one XLA keeps out of their fusion and
                        # feeds by recomputing the whole rule — a second pass
                        # over master, state and gradient, 44 bytes a
                        # parameter under Adam where this one pass moves 28
                        new_p.append(stn[0].astype(w.dtype))
                        new_opt.append(stn)
                    else:
                        # keep update arithmetic in the param dtype
                        wn, stn = rule.update(
                            opt, w, g.astype(w.dtype), st, lr, pwd, tt, rng_i)
                        new_p.append(keep(wn, w))
                        new_opt.append(tuple(keep(ns, s)
                                             for ns, s in zip(stn, st)))
                new_aux = tuple(keep(na, a) for na, a in zip(new_aux, araws))
            return tuple(new_p), tuple(new_opt), new_aux, loss, finite

        # shardings: batch over dp; params per rules; opt state reuses the
        # per-param state layout the update math is pinned to; aux replicated
        p_sh = tuple(self._spec_for(n) for n in self._param_names)
        # per-SLOT shardings: state slots can differ in rank from the
        # parameter (e.g. Nadam's scalar momentum schedule)
        opt_sh = tuple(
            tuple(self._state_spec_for(n, s.shape) for s in per)
            for n, per in zip(self._param_names, self._opt_raws))
        aux_sh = (self._mesh.replicated(),) * n_aux
        data_spec = ("dp",) + (None,) * (len(x_raw.shape) - 1)
        x_sh = self._mesh.sharding(*data_spec)
        y_sh = self._mesh.sharding("dp") if len(y_raw.shape) >= 1 \
            else self._mesh.replicated()
        rep = self._mesh.replicated()
        donate = (0, 1, 2) if self._donate else ()
        from .. import compile as _compile

        return _compile.jit(
            step_fn, site="trainer", token=self._service_token("step"),
            in_shardings=(p_sh, opt_sh, aux_sh, x_sh, y_sh, rep, rep,
                          rep),
            out_shardings=(p_sh, opt_sh, aux_sh, rep, rep),
            donate_argnums=donate)

    # -------------------------------------------------------------- step ---
    def step(self, x, y):
        """Run one compiled train step; returns the (replicated) loss.

        With ``nan_guard`` (the default) a step whose loss or gradients
        are non-finite leaves params/optimizer/aux untouched; after
        ``max_consecutive_skips`` such steps in a row a RuntimeError is
        raised (the step counter still advances on skipped steps — the
        step was attempted).

        With a ``trainer.step`` watchdog deadline armed
        (:mod:`mxnet_tpu.watchdog`) the whole step — dispatch, compile,
        and the nan_guard host read — is deadline-bounded: a wedged step
        writes a crash bundle and raises a catchable StallError (or
        checkpoints and aborts under ``action:abort``). NOTE the first
        step includes XLA compilation; size the deadline for it.

        Once a preemption drain has been requested
        (:mod:`mxnet_tpu.preempt` — SIGTERM received, or the ``preempt``
        fault mode fired) no NEW step may start: step raises
        :class:`~mxnet_tpu.preempt.DrainRequested` *before* dispatching,
        so the in-flight step is always the last one. Loops that poll
        ``preempt.requested()`` after each step drain before ever seeing
        the exception."""
        from .. import preempt as _preempt
        from .. import watchdog as _watchdog
        from ..telemetry import trace as _trace

        if _preempt.requested():
            raise _preempt.DrainRequested(_preempt.event())
        # the step's span (telemetry/trace.py): every piece of host work
        # below is a child of it, the watchdog's and telemetry's own
        # bookkeeping is what is left of it outside them
        with _trace.step(self._t + 1):
            return _watchdog.sync("trainer.step",
                                  lambda: self._step_impl(x, y),
                                  label=f"step {self._t + 1}")

    def _step_impl(self, x, y):
        from ..telemetry import steps as _tsteps
        from ..telemetry import trace as _trace

        # per-step phase timeline (data-wait / h2d / host / compute /
        # sync — docs/OBSERVABILITY.md): the record opens here, phases
        # accrue inside _step_exec, and a raising step (injected fault,
        # drain request, stall) abandons its partial record
        _tsteps.begin_step(self._t + 1)
        try:
            out = self._step_exec(x, y)
        except BaseException:
            _tsteps.abort()
            raise
        with _trace.span("trainer.bookkeeping"):
            _tsteps.end_step(flops=self._step_flops(),
                             devices=self._mesh.num_devices)
        if self._bus is not None and self._t % self._bus_every == 0:
            self.publish_update()
        return out

    def _step_flops(self):
        """XLA-analyzed flops per invocation of the compiled step (the
        ``mfu_xla`` numerator), or None before the compile service has
        captured a cost analysis for it."""
        from ..telemetry import costs as _tcosts

        token = getattr(self._step_fn, "_token_key", None)
        return _tcosts.flops_for(token) if token is not None else None

    def step_report(self):
        """The most recent step's telemetry record: duration, phase
        split, and (once cost analysis is captured) ``flops`` +
        ``mfu_xla``. None before the first completed step (or with
        telemetry disabled)."""
        from ..telemetry import steps as _tsteps

        return _tsteps.last()

    def _step_exec(self, x, y):
        from .. import compile as _compile
        from .. import faults as _faults
        from .. import random as _rand
        from ..telemetry import steps as _tsteps
        from ..telemetry.trace import span as _span

        x_raw = x._data if isinstance(x, NDArray) else x
        y_raw = y._data if isinstance(y, NDArray) else y
        if _faults.active():
            # 'trainer.step' injection: raise/delay/kill, or nan-poison
            # the batch (which the nan_guard must then absorb)
            x_raw = _faults.point("trainer.step", x_raw)
        if self._step_fn is None and self._distcheck:
            # distcheck auto-run BEFORE compile: full sharding surface
            # (params + optimizer-state layouts + batch dp divisibility)
            # — a misconfiguration fails here with a param-named Issue
            # list instead of an XLA error mid-compile
            from ..analysis import distcheck as _distcheck

            _distcheck.check_trainer(self, x_raw, y_raw)
        # every piece of host work below runs in a span of its own, and
        # the phase it feeds is that span's duration (one measurement).
        # With the guard's read the device has nothing queued from the
        # end of one step to the enqueue of the next, so each piece sits
        # where what it depends on puts it: up to trainer.dispatch only
        # what the launch needs, after trainer.guard_sync only what needs
        # the flag, the rest between the two, while the device runs
        with _span("trainer.put_batch") as sp:
            x_raw = self._put_batch(
                x_raw, self._mesh.sharding(
                    *(("dp",) + (None,) * (len(x_raw.shape) - 1))))
            y_raw = self._put_batch(y_raw, self._mesh.sharding("dp"))
        _tsteps.phase("h2d", sp.dur_ms)
        if self._step_fn is None:
            self._step_fn = self._build(x_raw, y_raw)
        self._t += 1
        # no program of their own ahead of the step's: t and lr are host
        # scalars of the step's dtypes and ride its launch, and the step
        # takes its key from the stream's current one itself
        with _span("trainer.scalars") as sp:
            t = _np.int32(self._t)
            lr = _np.float32(self._lr if self._lr_scheduler is None
                             else self._lr_scheduler(self._t))
            key = _rand.current_key()
        _tsteps.phase("host", sp.dur_ms)
        with _span("trainer.gather") as sp:
            self._adopt_outside_writes()
            in_p = tuple(h._data for h in self._train_handles)
            in_opt = self._opt_raws
            in_aux = tuple(h._data for h in self._aux_handles)
            known = self._remembered_signature(in_p, in_opt, in_aux)
        _tsteps.phase("host", sp.dur_ms)
        # the fused executable runs fwd+bwd+optimizer as one program, so
        # the optimizer phase is folded into compute (async dispatch:
        # device time lands in the nan-guard sync read below, or in the
        # next step's phases when nan_guard=False)
        with _span("trainer.dispatch") as sp:
            new_p, new_opt, new_aux, loss, ok = _compile.call_spanned(
                self._step_fn, in_p, in_opt, in_aux, x_raw, y_raw, key,
                t, lr, known=known)
        _tsteps.phase("compute", sp.dur_ms)
        with _span("trainer.rng_key") as sp:
            # the stream moves on as next_key() would have moved it; its
            # two small programs queue behind the step's
            _rand.advance()
        _tsteps.phase("compute", sp.dur_ms)
        with _span("trainer.commit") as sp:
            if self._donate and self._distcheck:
                # donation-safety (distcheck pass 3): the step donated
                # every param/opt/aux input buffer — poison them so a
                # stale alias used later raises a param-named
                # use-after-donate error instead of jax's anonymous
                # "Array has been deleted"
                from ..analysis import distcheck as _distcheck

                origin = "ShardedTrainer.step (donate=True)"
                for name, raw in zip(self._param_names, in_p):
                    _distcheck.mark_donated(raw, name, origin, self._t)
                for name, per in zip(self._param_names, in_opt):
                    for j, raw in enumerate(per):
                        _distcheck.mark_donated(
                            raw, f"{name} (optimizer state {j})", origin,
                            self._t)
                for name, raw in zip(self._aux_names, in_aux):
                    _distcheck.mark_donated(raw, name, origin, self._t)
            with autograd.pause():
                for h, raw in zip(self._train_handles, new_p):
                    h._data = raw  # donated buffers: rebind directly
                for h, raw in zip(self._aux_handles, new_aux):
                    h._data = raw
            self._opt_raws = new_opt
            self._pair_masters()
            # the next step's arguments are these outputs: walk their
            # ~600 leaves for its signature now, not ahead of its enqueue
            self._remember_signature(new_p, new_opt, new_aux)
        _tsteps.phase("host", sp.dur_ms)
        with _span("trainer.release") as sp:
            # the donated inputs die here, under a name and under the
            # running step: every array's destructor and the expiry
            # callback of its distcheck poison record
            del in_p, in_opt, in_aux
        _tsteps.phase("host", sp.dur_ms)
        if self._nan_guard:
            with _span("trainer.guard_sync") as sp:
                ok = bool(ok)  # blocks on step completion
            _tsteps.phase("sync", sp.dur_ms)
            self._account_skip(ok)
        return NDArray(loss)

    def _remember_signature(self, new_p, new_opt, new_aux):
        """Build the signature nodes of a step's outputs and remember
        them with weak references to every leaf (a strong one would keep
        a whole generation of parameters and state alive through a
        load_states or a set_data)."""
        from .. import compile as _compile

        nodes = _compile.signature((new_p, new_opt, new_aux))
        self._sig_memo = None if nodes is None else (
            tuple(map(weakref.ref, self._leaves(new_p, new_opt, new_aux))),
            tuple(map(len, new_opt)), nodes)

    def _remembered_signature(self, in_p, in_opt, in_aux):
        """The signature nodes built under the last step, if this step's
        arguments are, leaf by leaf and in the same grouping, the very
        arrays they were built from (an array's shape, dtype and sharding
        never change, so identity is enough); None after anything rebound
        a handle or the optimizer state (set_data, a loaded checkpoint, a
        reshard) and on the first step."""
        if self._sig_memo is None:
            return None
        refs, opt_lens, nodes = self._sig_memo
        leaves = self._leaves(in_p, in_opt, in_aux)
        if len(refs) == len(leaves) \
                and opt_lens == tuple(map(len, in_opt)) \
                and all(map(operator.is_, map(operator.call, refs), leaves)):
            return nodes
        return None

    @staticmethod
    def _leaves(p, opt, aux):
        return [*p, *itertools.chain.from_iterable(opt), *aux]

    def _account_skip(self, ok):
        from .. import profiler as _profiler

        if ok:
            self.consecutive_skips = 0
            return
        self.skipped_steps += 1
        self.consecutive_skips += 1
        _profiler.record_skip_step(self.skipped_steps,
                                   self.consecutive_skips)
        if self.consecutive_skips >= self._max_consecutive_skips:
            raise RuntimeError(
                f"ShardedTrainer: {self.consecutive_skips} consecutive "
                "steps produced non-finite loss/gradients and were "
                "skipped (step "
                f"{self._t}, {self.skipped_steps} skipped total) — the "
                "run has diverged; lower the learning rate, check the "
                "data pipeline, or resume from the last good checkpoint")

    def predict(self, x):
        """Compiled sharded inference forward (replicated output)."""
        import jax

        x_raw = x._data if isinstance(x, NDArray) else x
        x_raw = self._put_batch(
            x_raw, self._mesh.sharding(
                *(("dp",) + (None,) * (len(x_raw.shape) - 1))))
        if getattr(self, "_predict_fn", None) is None:
            net = self._net
            train_handles = self._train_handles
            aux_handles = self._aux_handles

            def fwd(praws, araws, x_):
                saved = [(h, h._data) for h in train_handles + aux_handles]
                try:
                    for h, r in zip(train_handles, praws):
                        h._data = r
                    for h, r in zip(aux_handles, araws):
                        h._data = r
                    with autograd.pause(train_mode=False):
                        out = net.forward(NDArray(x_))
                    return out._data
                finally:
                    for h, orig in saved:
                        h._data = orig

            p_sh = tuple(self._spec_for(n) for n in self._param_names)
            aux_sh = (self._mesh.replicated(),) * len(aux_handles)
            x_sh = self._mesh.sharding(
                *(("dp",) + (None,) * (len(x_raw.shape) - 1)))
            from .. import compile as _compile

            self._predict_fn = _compile.jit(
                fwd, site="trainer",
                token=self._service_token("predict"),
                in_shardings=(p_sh, aux_sh, x_sh),
                out_shardings=self._mesh.replicated())
        out = self._predict_fn(
            tuple(h._data for h in self._train_handles),
            tuple(h._data for h in self._aux_handles), x_raw)
        return NDArray(out)

    # -------------------------------------------------------- model bus ---
    def publish_to(self, bus, every=1, compress_threshold=None,
                   model=None, topk=None, rollback=True):
        """Stream live weight updates into a model bus: every `every`-th
        successful step publishes a version-stamped record of the
        current params (+ aux) into `bus` (a directory path or a
        :class:`~mxnet_tpu.modelbus.ModelBus`) for serving workers to
        apply between batches (docs/SERVING.md "Online updates").

        Small params ride as full tensors; params at or above
        `compress_threshold` elements ride int8 per-row compressed;
        `topk` ({param_name: k}) publishes only the k most-changed rows
        of the named (embedding-table-shaped) params. A non-finite
        update is never published (the nan-guard signal, re-checked at
        the bus). With `rollback` (default), a publish that finds the
        bus head quarantined by a subscriber first re-publishes the
        newest good version — the ROADMAP's "rollback = re-publish
        version N" contract.

        Returns the :class:`~mxnet_tpu.modelbus.ModelBus`.
        """
        from ..modelbus import ModelBus

        self._bus = bus if isinstance(bus, ModelBus) \
            else ModelBus(bus, compress_threshold=compress_threshold)
        self._bus_every = max(1, int(every))
        self._bus_rollback = bool(rollback)
        self._bus_model = model
        self._bus_topk = dict(topk) if topk else None
        return self._bus

    def publish_update(self):
        """Publish the current weights to the armed bus NOW (the per-K
        step hook calls this; explicit calls are fine too). Collective —
        every process gathers; only the writer rank writes. Returns the
        published version (None on non-writer ranks, a skipped
        non-finite update, or no armed bus)."""
        if self._bus is None:
            return None
        # host gathers are collective (ZeRO shards allgather) — run them
        # on EVERY process before the writer-rank gate
        params = [(n, self._host_copy(h._data))
                  for n, h in zip(self._param_names, self._train_handles)]
        aux = [(n, self._host_copy(h._data))
               for n, h in zip(self._aux_names, self._aux_handles)]
        if not self._is_writer_rank():
            return None
        if self._bus_rollback:
            self._bus.auto_rollback(worker="publisher")
        version = self._bus.publish(params, step=self._t, aux=aux,
                                    model=self._bus_model,
                                    topk=self._bus_topk)
        if version is not None:
            self.published_versions.append(version)
        return version

    # ------------------------------------------------------- checkpoint ---
    def _host_copy(self, arr):
        """Full host copy of a (possibly multi-host-sharded) array.
        Non-addressable shards (ZeRO state on other hosts) are gathered
        with a cross-process allgather."""
        import jax

        if getattr(arr, "is_fully_addressable", True) or \
                getattr(arr, "is_fully_replicated", False):
            return jax.device_get(arr)
        from jax.experimental import multihost_utils

        return multihost_utils.process_allgather(arr, tiled=True)

    def _ckpt_keys(self):
        """Expected entry keys, POSITIONAL (collect_params order) so a
        fresh process with fresh gluon auto-prefixes can resume."""
        keys = ["__t__", "__rng_seed__", "__rng_key__", "__names__"]
        if self._lr_scheduler is not None:
            keys.append("__sched__")
        keys += [f"p{i}" for i in range(len(self._param_names))]
        keys += [f"a{i}" for i in range(len(self._aux_names))]
        for i, per in enumerate(self._opt_raws):
            keys += [f"s{i}_{j}" for j in range(len(per))]
        return keys

    def _state_payload(self):
        """Assemble the full checkpoint payload as {key: NDArray}. Runs
        COLLECTIVELY on every process (the host copies allgather); the
        caller decides which rank writes."""
        import jax
        import jax.numpy as jnp

        from .. import random as _rand

        self._adopt_outside_writes()
        names_blob = "\n".join(self._param_names + self._aux_names)
        payload = {
            "__t__": NDArray(jnp.asarray(self._t, jnp.int32)),
            "__rng_seed__": NDArray(
                jnp.asarray(_rand.current_seed(), jnp.int32)),
            "__rng_key__": NDArray(jnp.asarray(
                jax.device_get(_rand.current_key()))),
            "__names__": NDArray(jnp.asarray(_np.frombuffer(
                names_blob.encode(), _np.uint8))),
        }
        if self._lr_scheduler is not None:
            # schedulers decay IN PLACE; resume must rewind their state
            import pickle

            payload["__sched__"] = NDArray(jnp.asarray(_np.frombuffer(
                pickle.dumps(self._lr_scheduler), _np.uint8)))
        for i, h in enumerate(self._train_handles):
            payload[f"p{i}"] = NDArray(self._host_copy(h._data))
        for i, h in enumerate(self._aux_handles):
            payload[f"a{i}"] = NDArray(self._host_copy(h._data))
        for i, per in enumerate(self._opt_raws):
            for j, s in enumerate(per):
                payload[f"s{i}_{j}"] = NDArray(self._host_copy(s))
        return payload

    def _is_writer_rank(self):
        """_host_copy's allgather is collective (every process runs it),
        but only one process may write a SHARED path; host-local
        trainers write regardless of rank."""
        import jax

        return not self._multiprocess or jax.process_index() == 0

    def save_states(self, fname):
        """Checkpoint params + optimizer state + step counter + the
        global RNG stream to one file in the `mx.nd.save` container
        (bf16 handled there as uint16 bits). Entries are positional,
        keyed by `collect_params()` order, so resuming into a freshly
        built identical architecture works even though gluon
        auto-prefixes differ between processes. The write is ATOMIC
        (tmp + fsync + os.replace): a run preempted mid-checkpoint
        leaves the previous state file intact, never a torn one.
        parity role: Trainer.save_states + model checkpoints
        (SURVEY §5.4)."""
        from ..checkpoint import atomic_write
        from ..ndarray import utils as nd_utils

        payload = self._state_payload()
        if self._is_writer_rank():
            atomic_write(fname, lambda tmp: nd_utils.save(tmp, payload))

    def topology_meta(self):
        """JSON-able topology record written into every checkpoint's
        MANIFEST entry (``meta.topology``): mesh shape, per-array
        sharding specs, and jax/device metadata. Arrays themselves are
        saved in CANONICAL HOST LAYOUT (full, gathered, C-order — see
        ``_host_copy``), so this record is *descriptive*: resume uses it
        to detect a topology change and reshard on load, never to
        interpret the bytes."""
        from .. import checkpoint as _ckpt

        return {
            "format": "canonical-host-v1",
            "mesh": self._mesh.describe(),
            "param_sharding": {n: list(self._rules.get(n, ()))
                               for n in self._param_names},
            "zero": self._zero,
            "host": _ckpt.host_metadata(),
        }

    def _remember_manager(self, manager, epoch, data_iter=None):
        """Track the newest manager/epoch (and the data iterator whose
        position rides in the checkpoint) and (re-)register the shared
        final-checkpoint hook (``watchdog.set_last_resort``) that both a
        watchdog ``action:abort`` and a preemption drain invoke. A hook
        the USER installed explicitly is never clobbered — only ours
        (tagged) is replaced as training advances."""
        from .. import watchdog as _watchdog

        self._ckpt_manager = manager
        self._ckpt_epoch = int(epoch)
        if data_iter is not None:
            self._ckpt_data_iter = data_iter
        prev = _watchdog.last_resort()
        if prev is None or getattr(prev, "_mxtpu_trainer_hook", False):
            hook = self._final_checkpoint
            try:
                hook.__func__._mxtpu_trainer_hook = True
            except AttributeError:
                pass
            _watchdog.set_last_resort(hook)

    def _final_checkpoint(self):
        """Last-resort/drain save: one more checkpoint through the
        remembered manager at epoch ``last+1`` with ``meta.drain`` set —
        the entry's ``step`` records the exact global step, which is the
        resume position for mid-epoch drains (data-position restore)."""
        mgr = self._ckpt_manager
        if mgr is None:
            return None
        from .. import preempt as _preempt

        meta = {"drain": _preempt.event() or True}
        return self.save_checkpoint(
            mgr, self._ckpt_epoch + 1, meta=meta,
            data_iter=getattr(self, "_ckpt_data_iter", None))

    def save_checkpoint(self, manager, epoch, meta=None, data_iter=None):
        """Write trainer state through a :class:`~mxnet_tpu.checkpoint.
        CheckpointManager` — atomic write, CRC-checksummed manifest entry,
        keep-N rotation, and a ``meta.topology`` record (mesh shape,
        per-array sharding specs, jax/device metadata) making the
        checkpoint topology-portable. Collective across processes; only
        the writer rank touches disk. Also registers this manager as the
        preemption-drain/last-resort target. Returns the manager's
        {name: path} map (None on non-writer ranks).

        ``data_iter``: an iterator with the ``state_dict()`` grammar
        (ImageRecordIter / TokenRecordIter / PrefetchingIter) — its exact
        stream position is recorded as ``meta.data_state`` and, once
        passed, rides in every later drain/last-resort checkpoint too, so
        a mid-epoch preemption resumes at the next unseen batch with the
        identical shuffle + augmentation stream."""
        from ..ndarray import utils as nd_utils

        payload = self._state_payload()
        meta = dict(meta or {})
        meta.setdefault("topology", self.topology_meta())
        if data_iter is not None and "data_state" not in meta:
            meta["data_state"] = data_iter.state_dict()
        self._remember_manager(manager, epoch, data_iter)
        if not self._is_writer_rank():
            return None
        return manager.save(
            epoch, {"states": lambda tmp: nd_utils.save(tmp, payload)},
            step=self._t, meta=meta)

    @staticmethod
    def _topology_changed(saved, current):
        """Human-readable mismatch list between two topology records
        (empty = bit-exact-resume territory)."""
        diffs = []
        sm, cm = saved.get("mesh") or {}, current.get("mesh") or {}
        if sm.get("axes") != cm.get("axes"):
            diffs.append(f"mesh axes {sm.get('axes')} -> {cm.get('axes')}")
        if sm.get("num_devices") != cm.get("num_devices"):
            diffs.append(f"device count {sm.get('num_devices')} -> "
                         f"{cm.get('num_devices')}")
        sh, ch = saved.get("host") or {}, current.get("host") or {}
        if sh.get("process_count") != ch.get("process_count"):
            diffs.append(f"process count {sh.get('process_count')} -> "
                         f"{ch.get('process_count')}")
        return diffs

    def resume(self, manager, reshard=None, data_iter=None):
        """Restore the latest good checkpoint recorded by `manager`
        (corrupt files are detected by checksum and skipped in favour of
        the previous good epoch). Returns the manifest entry — epoch,
        step, meta — or None when the manager records no checkpoint yet
        (fresh start).

        Topology portability: the entry's ``meta.topology`` is compared
        against this trainer's mesh. On a MATCH the restore is bit-exact
        (same arrays, same layout, same RNG stream). On a MISMATCH the
        checkpoint — stored in canonical host layout — is **resharded on
        load**: every array (params, aux, and sharded/ZeRO optimizer
        state) is re-placed through THIS mesh's sharding rules, the RNG
        stream continues from the saved position (keys are host-side and
        fold in step/param indices, never device ids, so the sample
        stream is device-count independent), and the entry's ``step`` is
        the data position to resume from. Numerics then match the
        uninterrupted run up to XLA reduction-order differences — not
        bit-exact. Pass ``reshard=False`` (or set
        ``MXNET_TPU_PREEMPT_RESHARD=0``) to forbid cross-topology resume;
        a mismatch then raises a mesh-naming ValueError."""
        import os as _os

        res = manager.resume()
        if res is None:
            return None
        entry, paths = res
        saved_topo = (entry.get("meta") or {}).get("topology")
        if saved_topo:
            current = self.topology_meta()
            diffs = self._topology_changed(saved_topo, current)
            if diffs:
                if reshard is None:
                    reshard = _os.environ.get(
                        "MXNET_TPU_PREEMPT_RESHARD", "1") != "0"
                saved_mesh = (saved_topo.get("mesh") or {}).get("axes")
                if not reshard:
                    # name the axes precisely: a typo'd axis on the new
                    # mesh gets a did-you-mean hint + the valid axis list
                    # (the shared difflib helper via mesh.axis_error)
                    axis_notes = "".join(
                        "; saved " + self._mesh.axis_error(a)
                        for a in sorted(saved_mesh or {})
                        if a not in self._mesh.axis_sizes)
                    raise ValueError(
                        f"checkpoint epoch {entry['epoch']} was written on "
                        f"DeviceMesh({saved_mesh}) but this trainer runs on "
                        f"{self._mesh!r} ({'; '.join(diffs)}{axis_notes}) "
                        "and resharding "
                        "is disabled — resume on the original topology, or "
                        "allow resharding (reshard=True / unset "
                        "MXNET_TPU_PREEMPT_RESHARD=0) to re-place the "
                        "canonical-layout arrays on the new mesh")
                import warnings

                warnings.warn(
                    f"resuming checkpoint epoch {entry['epoch']} across a "
                    f"topology change ({'; '.join(diffs)}): arrays reshard "
                    f"from DeviceMesh({saved_mesh}) onto {self._mesh!r}; "
                    "numerics match the original trajectory up to XLA "
                    "reduction order (bit-exact only on the saved "
                    "topology)", stacklevel=2)
        self.load_states(paths["states"])
        data_state = (entry.get("meta") or {}).get("data_state")
        if data_iter is not None and data_state is not None:
            # restore the exact stream position the checkpoint was cut at
            # — load_state_dict re-partitions it when this gang's
            # num_parts differs from the saving gang's (resharded resume)
            data_iter.load_state_dict(data_state)
        self._remember_manager(manager, entry["epoch"],
                               data_iter=data_iter)
        return entry

    def load_states(self, fname):
        """Restore a `save_states` checkpoint, re-laying every tensor out
        on this trainer's mesh (mesh/rules/ZeRO layout may differ from
        the saving run — resharding is just a fresh device_put). Also
        restores the global RNG stream, so a resumed run reproduces the
        uninterrupted run's sample stream exactly. The key set AND every
        tensor shape are validated before anything is mutated — a failed
        load never leaves the trainer half-restored."""
        import os

        import jax

        from .. import random as _rand
        from ..ndarray import utils as nd_utils

        if not os.path.exists(fname):
            raise FileNotFoundError(
                f"trainer state file not found: {fname!r}")
        try:
            arrays = nd_utils.load(fname)
        except Exception as e:
            raise ValueError(
                f"corrupt trainer state file {fname!r}: "
                f"{type(e).__name__}: {e} (truncated write? load through "
                "CheckpointManager.resume to fall back to the previous "
                "good checkpoint)") from e
        expected = set(self._ckpt_keys())
        got = set(arrays)
        if expected != got:
            raise ValueError(
                "checkpoint does not match this trainer: missing "
                f"{sorted(expected - got)[:5]}, unexpected "
                f"{sorted(got - expected)[:5]} (param count or optimizer "
                "differs)")
        shape_of = {}
        for i, h in enumerate(self._train_handles):
            shape_of[f"p{i}"] = tuple(h._data.shape)
        for i, h in enumerate(self._aux_handles):
            shape_of[f"a{i}"] = tuple(h._data.shape)
        for i, per in enumerate(self._opt_raws):
            for j, s in enumerate(per):
                shape_of[f"s{i}_{j}"] = tuple(s.shape)
        bad = [(k, tuple(arrays[k].shape), want)
               for k, want in shape_of.items()
               if tuple(arrays[k].shape) != want]
        if bad:
            k, got_s, want_s = bad[0]
            raise ValueError(
                f"checkpoint does not match this trainer: entry {k!r} "
                f"has shape {got_s}, trainer expects {want_s} "
                f"(saved param order: "
                f"{bytes(_np.asarray(arrays['__names__']._data)).decode()})")

        def take(key, want_dtype, spec):
            # _global_put handles multi-host meshes (plain device_put
            # cannot target non-addressable devices)
            return self._global_put(
                arrays[key]._data.astype(want_dtype), spec)

        self._t = int(arrays["__t__"].asscalar())
        if self._lr_scheduler is not None:
            import pickle

            self._lr_scheduler = pickle.loads(
                bytes(_np.asarray(arrays["__sched__"]._data)))
        _rand._ensure()
        _rand._state.seed = int(arrays["__rng_seed__"].asscalar())
        _rand._state.key = arrays["__rng_key__"]._data
        for i, (name, h) in enumerate(zip(self._param_names,
                                          self._train_handles)):
            # a mastered parameter is the cast of its saved master, the
            # saved parameter itself unless something wrote it behind
            # the saving trainer's back (_adopt_outside_writes)
            key = f"s{i}_0" if i in self._mastered else f"p{i}"
            h._rebind(take(key, h._data.dtype, self._spec_for(name)))
        for i, h in enumerate(self._aux_handles):
            h._rebind(take(f"a{i}", h._data.dtype, self._mesh.replicated()))
        self._opt_raws = tuple(
            tuple(take(f"s{i}_{j}", s.dtype,
                       self._state_spec_for(name, s.shape))
                  for j, s in enumerate(per))
            for i, (name, per) in enumerate(zip(self._param_names,
                                                self._opt_raws)))
        self._pair_masters()

    def unshard(self, ctx=None):
        """Gather parameters back to one device for eager/export use."""
        import jax

        from ..context import current_context

        dev = (ctx or current_context()).jax_device()
        self._adopt_outside_writes()
        for h in self._train_handles + self._aux_handles:
            h._rebind(jax.device_put(self._host_copy(h._data), dev))
        self._pair_masters()

    @property
    def mesh(self):
        return self._mesh


#: ``jax.named_scope``s inside the compiled step: the two parts jax does not
#: name itself (it marks forward ``jvp(`` and backward ``transpose(jvp(``).
#: They reach the optimized HLO's ``op_name`` metadata only, never the cache
#: key, and ``chipbench/harness/step_phases.py`` reads them by name. A scope
#: around anything that holds a Pallas call would rename the kernel in its
#: payload and compile that step anew. (Down here, and both scopes below the
#: ``grads_of`` call, because that payload also holds the file and LINE of
#: the kernel's Python callers, ``grads_of`` and ``step_fn`` among them for
#: a backward kernel: a line added above them compiles every step with a
#: Pallas call anew, once.)
GUARD_SCOPE = "trainer.guard"
UPDATE_SCOPE = "trainer.update"
