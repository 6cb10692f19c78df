"""RNG state management.

Reference surface: paddle.seed / Generator (python/paddle/framework/random.py).
TPU-native design: splittable jax PRNG keys.  Eager code consumes keys from a
global seeded stream; traced code (to_static / fused train steps) pushes a
*traced* key via ``key_context`` so randomness is a real input to the XLA
program instead of a baked-in constant — this is what keeps dropout correct
across jitted steps.
"""
from __future__ import annotations

import contextlib

import jax

_state = {"key": None, "seed": 0}
_key_stack: list = []


def seed(s: int):
    _state["key"] = jax.random.PRNGKey(int(s))
    _state["seed"] = int(s)
    return s


def default_key():
    if _state["key"] is None:
        seed(0)
    return _state["key"]


def next_key():
    """Return a fresh PRNG key; safe both eagerly and under tracing."""
    if _key_stack:
        k, sub = jax.random.split(_key_stack[-1])
        _key_stack[-1] = k
        return sub
    k, sub = jax.random.split(default_key())
    _state["key"] = k
    return sub


def peek_key():
    """A key with the aval `next_key()` returns, WITHOUT advancing the
    stream — for lowering a program that takes a key (a draw would
    perturb later training randomness)."""
    return jax.random.split(default_key())[1]


@contextlib.contextmanager
def key_context(key):
    """Route next_key() to splits of `key` (used by jit/functional paths)."""
    _key_stack.append(key)
    try:
        yield
    finally:
        _key_stack.pop()


def get_rng_state():
    return {"key": default_key(), "seed": _state["seed"]}


def set_rng_state(st):
    _state["key"] = st["key"]
    _state["seed"] = st.get("seed", 0)
