"""Stateful random number generation over JAX's functional PRNG.

Parity target: `RandGenerator<cpu/gpu>` in the reference
(`include/mxnet/random_generator.h:42-141`): per-device stateful generators
(1024 mt19937 / curand Philox states) seeded by `mx.random.seed`.

TPU-native: JAX PRNG is functional (threefry keys). This module owns the
*stateful* wrapper: a global seed + a split counter. Every imperative random
op draws `next_key()`; hybridized graphs receive a key as an extra traced
input so the compiled executable stays pure. `seed()` resets the stream
(optionally per-context, matching `mx.random.seed(..., ctx=...)`).

The keys are threefry keys (``uint32[2]``) and the sampling ops draw with
threefry, the same values on every backend. `Dropout` alone takes its
mask's words from XLA's bit generator (`jax.lax.rng_bit_generator`, the
backend's default algorithm) seeded by the key it is handed: reproducible
on one backend from `mx.random.seed`, NOT the same stream on CPU and TPU
(the reference's Dropout differed between cuDNN and the CPU too).
"""
from __future__ import annotations

import threading

__all__ = ["seed", "next_key", "current_key", "advance", "current_seed"]

_state = threading.local()


def _ensure():
    if not hasattr(_state, "key"):
        import jax

        _state.seed = 0
        _state.key = jax.random.PRNGKey(0)


def seed(seed_state: int, ctx=None) -> None:
    """Seed the global generator (parity: mx.random.seed)."""
    import jax

    _state.seed = int(seed_state)
    _state.key = jax.random.PRNGKey(int(seed_state))


def current_seed() -> int:
    _ensure()
    return _state.seed


def next_key():
    """Draw a fresh PRNG key, advancing the global stream.

    Inside a CachedOp trace, keys come from the scope's traced key input so
    compiled graphs stay pure yet advance with the global stream per call."""
    import jax

    from . import cached_op

    scope = cached_op.current_trace()
    if scope is not None:
        return scope.next_key()
    _ensure()
    _state.key, sub = jax.random.split(_state.key)
    return sub


def current_key():
    """The global stream's key as it stands, NOT advanced: for a compiled
    program that takes ``jax.random.split(key)[1]`` itself (the draw
    :func:`next_key` would have handed it) while its caller moves the
    stream on with :func:`advance` once the program is enqueued."""
    _ensure()
    return _state.key


def advance():
    """Move the global stream on by one draw without taking it: what
    :func:`next_key` does to the stream, the same two device programs."""
    import jax

    _ensure()
    _state.key, _ = jax.random.split(_state.key)
