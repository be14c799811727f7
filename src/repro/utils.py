"""Small shared utilities: pytree math, PRNG fan-out, parameter counting."""
from __future__ import annotations

import functools
import os
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


class Registry(dict):
    """One generic name -> implementation table for every pluggable seam.

    The strategy / aggregator / wire-codec / failure-model / server-
    optimizer registries used to be copy-pasted dict + decorator +
    resolver triples whose unknown-name errors drifted apart; this class
    is the single implementation. It IS a dict — existing call sites like
    ``sorted(engine.STRATEGIES)`` or ``"mean" in AGGREGATORS`` keep
    working — plus:

    * ``register(name, **attrs)`` — decorator factory; stamps ``attrs``
      on the function (``strategy_name``, ``needs_deltas``, ...) and
      refuses duplicate names.
    * ``resolve(name)`` — the canonical registered name with the seam's
      aliases applied (e.g. aggregator ``None``/``"none"`` -> ``"mean"``).
    * ``lookup(name)`` — resolve + fetch, raising the ONE consistent
      unknown-name error that lists the valid entries.
    * ``names()`` — sorted registered names (what the error shows).
    """

    def __init__(self, kind: str, *, aliases: dict | None = None):
        super().__init__()
        self.kind = kind
        self.aliases = dict(aliases or {})

    def register(self, name: str, **attrs):
        def deco(fn):
            if name in self:
                raise ValueError(f"duplicate {self.kind} {name!r}")
            for k, v in attrs.items():
                setattr(fn, k, v)
            self[name] = fn
            return fn
        return deco

    def resolve(self, name):
        return self.aliases.get(name, name)

    def lookup(self, name):
        canonical = self.resolve(name)
        if canonical not in self:
            raise ValueError(
                f"unknown {self.kind} {name!r}; registered: {self.names()}")
        return self[canonical]

    def names(self) -> list:
        return sorted(self)


def tree_zeros_like(tree: Pytree) -> Pytree:
    return jax.tree.map(jnp.zeros_like, tree)


def tree_add(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(tree: Pytree, s) -> Pytree:
    return jax.tree.map(lambda x: x * s, tree)


def tree_axpy(a, x: Pytree, y: Pytree) -> Pytree:
    """a * x + y, leafwise."""
    return jax.tree.map(lambda xi, yi: a * xi + yi, x, y)


def tree_dot(a: Pytree, b: Pytree) -> jax.Array:
    leaves = jax.tree.map(lambda x, y: jnp.vdot(x, y), a, b)
    return functools.reduce(jnp.add, jax.tree.leaves(leaves))


def tree_sq_norm(tree: Pytree) -> jax.Array:
    return tree_dot(tree, tree)


def tree_cast(tree: Pytree, dtype) -> Pytree:
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def param_count(tree: Pytree) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree.leaves(tree)))


def param_bytes(tree: Pytree) -> int:
    return int(sum(np.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(tree)))


def fold_in_name(key: jax.Array, name: str) -> jax.Array:
    """Derive a named sub-key deterministically from a string.

    Uses crc32, NOT python's builtin ``hash`` — str hashing is salted per
    process (PYTHONHASHSEED), so builtin-hash-derived keys silently gave
    every process a different "seeded" model init: benchmark loss curves
    and paper runs were unreproducible across invocations."""
    h = np.uint32(zlib.crc32(name.encode()) % (2**31 - 1))
    return jax.random.fold_in(key, h)


def split_like(key: jax.Array, names: list[str]) -> dict[str, jax.Array]:
    return {n: fold_in_name(key, n) for n in names}


def has_nan(tree: Pytree) -> jax.Array:
    leaves = [jnp.any(jnp.isnan(x)) for x in jax.tree.leaves(tree) if jnp.issubdtype(x.dtype, jnp.floating)]
    return functools.reduce(jnp.logical_or, leaves, jnp.asarray(False))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache lives at ``<checkout>/.jax_cache``,
    a fixed path: the path is part of the cache key, so a temporary or
    per-process directory would never hit. Called from entry points only,
    never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return ceil_div(a, b) * b
