"""Per-optimizer update rules for the compiled ShardedTrainer step.

Bridges the eager optimizer zoo (``optimizer/optimizer.py``, 17 entries —
parity: python/mxnet/optimizer/optimizer.py) into the ONE-executable
sharded train step. Each rule supplies

  init(opt, w)                         -> tuple of fresh state buffers
  update(opt, w, g, st, lr, wd, t, rng) -> (new_w, new_states)

reusing the jitted kernels from ``ops/optimizer_op.py`` (parity:
src/operator/optimizer_op.cc:49-970) so the compiled step and the eager
Trainer produce identical numerics. Hyper-parameters are read from the
eager Optimizer instance at trace time (static, baked into the
executable); ``lr`` and ``t`` arrive as traced float32 scalars so lr
schedules and bias-correction never retrace; ``rng`` feeds stochastic
rules (SGLD).

Rule contract details:
- ``g`` arrives in the update arithmetic dtype (the weight dtype, or
  float32 under multi-precision — ShardedTrainer handles the master-copy
  wrapping before calling the rule).
- Rules that scale ``lr`` by traced-``t`` factors compute the effective
  lr in float32, then ``_lr_of`` casts it to the weight dtype so bf16
  parameters are never silently promoted.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops import optimizer_op as K

__all__ = ["RULES", "Rule"]

RULES = {}


class Rule:
    def __init__(self, init, update):
        self.init = init
        self.update = update


def _register(names, init, update):
    for n in names:
        RULES[n] = Rule(init, update)


def _zeros(w, n):
    return tuple(jnp.zeros(w.shape, w.dtype) for _ in range(n))


def _clip(opt):
    return opt.clip_gradient if opt.clip_gradient else -1.0


def _lr_of(lr, w):
    return lr.astype(w.dtype) if hasattr(lr, "astype") else lr


def _prep(opt, g, w, wd, with_wd=False):
    """SGD/SGLD-family gradient prep: rescale, clip, THEN optionally add
    wd*w (the eager SGLD ordering)."""
    g = g * opt.rescale_grad
    if opt.clip_gradient:
        g = jnp.clip(g, -opt.clip_gradient, opt.clip_gradient)
    return g + wd * w if with_wd else g


def _prep_wd_then_clip(opt, g, w, wd):
    """Adam-family prep: wd*w folded in BEFORE the clip (eager Adamax/
    Nadam ordering, same as ops.optimizer_op._prep_grad_wd)."""
    g = g * opt.rescale_grad + wd * w
    if opt.clip_gradient:
        g = jnp.clip(g, -opt.clip_gradient, opt.clip_gradient)
    return g


def _mom_init(opt, w):
    return _zeros(w, 1) if opt.momentum else ()


# ------------------------------------------------------------ SGD family ---

def _sgd_update(opt, w, g, st, lr, wd, t, rng):
    kw = dict(lr=_lr_of(lr, w), wd=wd, rescale_grad=opt.rescale_grad,
              clip_gradient=_clip(opt))
    if opt.momentum:
        w2, m2 = K.sgd_mom_update.fn(w, g, st[0], momentum=opt.momentum,
                                     **kw)
        return w2, (m2,)
    return K.sgd_update.fn(w, g, **kw), ()


def _nag_update(opt, w, g, st, lr, wd, t, rng):
    kw = dict(lr=_lr_of(lr, w), wd=wd, rescale_grad=opt.rescale_grad,
              clip_gradient=_clip(opt))
    if opt.momentum:
        w2, m2 = K.nag_mom_update.fn(w, g, st[0], momentum=opt.momentum,
                                     **kw)
        return w2, (m2,)
    return K.sgd_update.fn(w, g, **kw), ()


def _signum_update(opt, w, g, st, lr, wd, t, rng):
    kw = dict(lr=_lr_of(lr, w), wd=wd, rescale_grad=opt.rescale_grad,
              clip_gradient=_clip(opt))
    if opt.momentum:
        w2, m2 = K.signum_update.fn(w, g, st[0], momentum=opt.momentum,
                                    wd_lh=opt.wd_lh, **kw)
        return w2, (m2,)
    return K.signsgd_update.fn(w, g, **kw), ()


def _lars_update(opt, w, g, st, lr, wd, t, rng):
    kw = dict(lr=_lr_of(lr, w), eta=opt.eta, epsilon=opt.epsilon, wd=wd,
              rescale_grad=opt.rescale_grad, clip_gradient=_clip(opt))
    if opt.momentum:
        w2, m2 = K.lars_sgd_mom_update.fn(w, g, st[0],
                                          momentum=opt.momentum, **kw)
        return w2, (m2,)
    return K.lars_sgd_update.fn(w, g, **kw), ()


def _sgld_update(opt, w, g, st, lr, wd, t, rng):
    g = _prep(opt, g, w, wd, with_wd=True)
    lr_w = _lr_of(lr, w)
    noise = jax.random.normal(rng, w.shape, w.dtype) * jnp.sqrt(lr_w)
    return w - lr_w / 2 * g + noise, ()


def _lbsgd_update(opt, w, g, st, lr, wd, t, rng):
    """LBSGD warmup multiplier from traced t. The eager optimizer's
    batch_scale gradient accumulation is subsumed by ShardedTrainer's
    accum_steps (one compiled scan); rules see per-step gradients."""
    nwup = float(opt.warmup_epochs * opt.updates_per_epoch)
    maxmult = float(opt.batch_scale)
    if opt.warmup_strategy == "lars":
        # trust ratio from the RAW gradient (eager _get_lars gets the
        # unrescaled accumulated grad); the step uses the prepped one
        w2s = jnp.sum(jnp.square(w))
        g2s = jnp.sum(jnp.square(g))
        mult = jnp.clip(jnp.sqrt(w2s / (g2s + wd * w2s + 1e-18)),
                        0.01, 100.0)
        g = _prep(opt, g, w, wd)
        step = (_lr_of(lr, w) * mult.astype(w.dtype)) * (g + wd * w)
        if opt.momentum:
            m2 = opt.momentum * st[0] - step
            return w + m2, (m2,)
        return w - step, ()
    tt = t + float(opt.init_updates)
    if nwup <= 1:
        # eager _get_lbmult: nup >= nwup wins first, so a zero/one-step
        # warmup window means the full batch_scale multiplier from the
        # first update
        mult = jnp.float32(maxmult)
    else:
        if opt.warmup_strategy == "linear":
            mult = 1.0 + (maxmult - 1) * tt / nwup
        elif opt.warmup_strategy == "power2":
            mult = 1.0 + (maxmult - 1) * (tt * tt) / (nwup * nwup)
        elif opt.warmup_strategy == "sqrt":
            mult = 1.0 + (maxmult - 1) * jnp.sqrt(tt / nwup)
        else:
            mult = jnp.float32(1.0)
        mult = jnp.where(tt >= nwup, maxmult, mult)
    kw = dict(lr=_lr_of(lr * mult, w), wd=wd,
              rescale_grad=opt.rescale_grad, clip_gradient=_clip(opt))
    if opt.momentum:
        w2, m2 = K.sgd_mom_update.fn(w, g, st[0], momentum=opt.momentum,
                                     **kw)
        return w2, (m2,)
    return K.sgd_update.fn(w, g, **kw), ()


def _dcasgd_init(opt, w):
    prev = jnp.array(w)
    return (_zeros(w, 1) + (prev,)) if opt.momentum else (prev,)


def _dcasgd_update(opt, w, g, st, lr, wd, t, rng):
    g = _prep(opt, g, w, wd)
    prev = st[-1]
    lr_w = _lr_of(lr, w)
    delta = -lr_w * (g + wd * w + opt.lamda * g * g * (w - prev))
    if opt.momentum:
        m2 = opt.momentum * st[0] + delta
        return w + m2, (m2, w)
    return w + delta, (w,)


# ----------------------------------------------------------- Adam family ---

def _adam_update(opt, w, g, st, lr, wd, t, rng):
    # bias correction folded into lr (reference Adam semantics)
    lr_eff = lr * jnp.sqrt(1.0 - opt.beta2 ** t) / (1.0 - opt.beta1 ** t)
    w2, m2, v2 = K.adam_update.fn(
        w, g, st[0], st[1], lr=_lr_of(lr_eff, w), beta1=opt.beta1,
        beta2=opt.beta2, epsilon=opt.epsilon, wd=wd,
        rescale_grad=opt.rescale_grad, clip_gradient=_clip(opt))
    return w2, (m2, v2)


def _ftml_update(opt, w, g, st, lr, wd, t, rng):
    w2, d2, v2, z2 = K.ftml_update.fn(
        w, g, st[0], st[1], st[2], lr=_lr_of(lr, w), beta1=opt.beta1,
        beta2=opt.beta2, epsilon=opt.epsilon, wd=wd,
        rescale_grad=opt.rescale_grad, clip_grad=_clip(opt), t=t)
    return w2, (d2, v2, z2)


def _lamb_update(opt, w, g, st, lr, wd, t, rng):
    upd, m2, v2 = K.lamb_update_phase1.fn(
        w, g, st[0], st[1], beta1=opt.beta1, beta2=opt.beta2,
        epsilon=opt.epsilon, t=t, bias_correction=opt.bias_correction,
        wd=wd, rescale_grad=opt.rescale_grad, clip_gradient=_clip(opt))
    r1 = jnp.sqrt(jnp.sum(jnp.square(w)))
    r2 = jnp.sqrt(jnp.sum(jnp.square(upd)))
    w2 = K.lamb_update_phase2.fn(
        w, upd, r1, r2, lr=_lr_of(lr, w),
        lower_bound=opt.lower_bound if opt.lower_bound else -1.0,
        upper_bound=opt.upper_bound if opt.upper_bound else -1.0)
    return w2, (m2, v2)


def _adagrad_update(opt, w, g, st, lr, wd, t, rng):
    w2, h2 = K.adagrad_update.fn(
        w, g, st[0], lr=_lr_of(lr, w), epsilon=opt.float_stable_eps,
        wd=wd, rescale_grad=opt.rescale_grad, clip_gradient=_clip(opt))
    return w2, (h2,)


def _rmsprop_init(opt, w):
    return _zeros(w, 3 if opt.centered else 1)


def _rmsprop_update(opt, w, g, st, lr, wd, t, rng):
    kw = dict(lr=_lr_of(lr, w), gamma1=opt.gamma1, epsilon=opt.epsilon,
              wd=wd, rescale_grad=opt.rescale_grad,
              clip_gradient=_clip(opt),
              clip_weights=opt.clip_weights if opt.clip_weights else -1.0)
    if opt.centered:
        w2, n2, g2, d2 = K.rmspropalex_update.fn(
            w, g, st[0], st[1], st[2], gamma2=opt.gamma2, **kw)
        return w2, (n2, g2, d2)
    w2, n2 = K.rmsprop_update.fn(w, g, st[0], **kw)
    return w2, (n2,)


def _adadelta_update(opt, w, g, st, lr, wd, t, rng):
    w2, a2, d2 = K.adadelta_update.fn(
        w, g, st[0], st[1], rho=opt.rho, epsilon=opt.epsilon, wd=wd,
        rescale_grad=opt.rescale_grad, clip_gradient=_clip(opt))
    return w2, (a2, d2)


def _ftrl_update(opt, w, g, st, lr, wd, t, rng):
    w2, z2, n2 = K.ftrl_update.fn(
        w, g, st[0], st[1], lr=_lr_of(lr, w), lamda1=opt.lamda1,
        beta=opt.beta, wd=wd, rescale_grad=opt.rescale_grad,
        clip_gradient=_clip(opt))
    return w2, (z2, n2)


def _adamax_update(opt, w, g, st, lr, wd, t, rng):
    g = _prep_wd_then_clip(opt, g, w, wd)
    m2 = opt.beta1 * st[0] + (1.0 - opt.beta1) * g
    u2 = jnp.maximum(opt.beta2 * st[1], jnp.abs(g))
    lr_eff = _lr_of(lr / (1.0 - opt.beta1 ** t), w)
    return w - lr_eff * m2 / (u2 + 1e-8), (m2, u2)


def _nadam_init(opt, w):
    # third slot: the cumulative momentum schedule, carried PER PARAMETER
    # (the eager reference shares one m_schedule float across all params,
    # an order-dependent wart; per-param is the faithful per-tensor math
    # and matches eager exactly for the t-th update of each param trained
    # every step)
    return _zeros(w, 2) + (jnp.ones((), jnp.float32),)


def _nadam_update(opt, w, g, st, lr, wd, t, rng):
    g = _prep_wd_then_clip(opt, g, w, wd)
    psi = opt.schedule_decay
    mom_t = opt.beta1 * (1.0 - 0.5 * 0.96 ** (t * psi))
    mom_t1 = opt.beta1 * (1.0 - 0.5 * 0.96 ** ((t + 1.0) * psi))
    sched = st[2] * mom_t
    sched_next = sched * mom_t1
    m2 = opt.beta1 * st[0] + (1.0 - opt.beta1) * g
    v2 = opt.beta2 * st[1] + (1.0 - opt.beta2) * g * g
    g_prime = g / (1.0 - sched).astype(w.dtype)
    m_prime = m2 / (1.0 - sched_next).astype(w.dtype)
    v_prime = v2 / (1.0 - opt.beta2 ** t).astype(w.dtype)
    m_bar = ((1.0 - mom_t).astype(w.dtype) * g_prime
             + mom_t1.astype(w.dtype) * m_prime)
    w2 = w - _lr_of(lr, w) * m_bar / (jnp.sqrt(v_prime) + opt.epsilon)
    return w2, (m2, v2, sched)


def _test_update(opt, w, g, st, lr, wd, t, rng):
    w2 = w - g * opt.rescale_grad * _lr_of(lr, w)
    return w2, (w2,)


_register(["sgd"], _mom_init, _sgd_update)
_register(["nag"], _mom_init, _nag_update)
_register(["signum", "signsgd"], _mom_init, _signum_update)
_register(["lars"], _mom_init, _lars_update)
_register(["sgld"], lambda opt, w: (), _sgld_update)
_register(["lbsgd"], _mom_init, _lbsgd_update)
_register(["dcasgd"], _dcasgd_init, _dcasgd_update)
_register(["adam"], lambda opt, w: _zeros(w, 2), _adam_update)
_register(["ftml"], lambda opt, w: _zeros(w, 3), _ftml_update)
_register(["lamb"], lambda opt, w: _zeros(w, 2), _lamb_update)
_register(["adagrad"], lambda opt, w: _zeros(w, 1), _adagrad_update)
_register(["rmsprop"], _rmsprop_init, _rmsprop_update)
_register(["adadelta"], lambda opt, w: _zeros(w, 2), _adadelta_update)
_register(["ftrl"], lambda opt, w: _zeros(w, 2), _ftrl_update)
_register(["adamax"], lambda opt, w: _zeros(w, 2), _adamax_update)
_register(["nadam"], _nadam_init, _nadam_update)
_register(["test"], lambda opt, w: _zeros(w, 1), _test_update)
