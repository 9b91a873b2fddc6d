"""Global RNG state management.

The reference keeps per-device stateful generators
(reference: python/paddle/framework/random.py, paddle/phi/core/generator.h).
JAX RNG is functional; we bridge with a host-side stateful key that is split
on every random op. Under jit tracing, code should push a traced key via
:func:`rng_guard` (the jit/train-step builders do this) so random ops stay
functional inside the compiled program.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

import jax

__all__ = ["seed", "get_rng_state", "set_rng_state", "next_key", "rng_guard"]


class _RNGState(threading.local):
    def __init__(self):
        # built on first use: creating a key initialises the JAX backend,
        # and importing this package must not claim the chip
        self._key = None
        self.traced_stack = []  # keys pushed by jit tracing contexts
        self.counter = 0

    @property
    def key(self):
        if self._key is None:
            self._key = jax.random.PRNGKey(0)
        return self._key

    @key.setter
    def key(self, value):
        self._key = value


_state = _RNGState()


def seed(s: int):
    _state.key = jax.random.PRNGKey(int(s))
    _state.counter = 0
    return _state.key


def get_rng_state():
    return _state.key


def set_rng_state(key):
    _state.key = key


def next_key():
    """Split one subkey off the active generator (traced key if inside
    rng_guard, else the global host key)."""
    if _state.traced_stack:
        _state.counter += 1
        return jax.random.fold_in(_state.traced_stack[-1], _state.counter)
    _state.key, sub = jax.random.split(_state.key)
    return sub


@contextmanager
def rng_guard(key):
    """Route next_key() to fold-ins of ``key`` (used while tracing jit fns)."""
    _state.traced_stack.append(key)
    saved = _state.counter
    _state.counter = 0
    try:
        yield
    finally:
        _state.traced_stack.pop()
        _state.counter = saved
