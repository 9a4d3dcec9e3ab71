"""Seeded data for the benchmark, made on the device.

The two generators are the benchmark's own copies of the
Borzsonyi-Kossmann-Stocker conventions (ICDE 2001) that the program
ships in `repro.core.datagen`: points in [0, 1]^d, smaller is better.
They are copied so that a change to the program cannot move the
yardstick's inputs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def uniform(key, n: int, d: int):
    """Independent U[0, 1) per attribute."""
    return jax.random.uniform(key, (n, d), jnp.float32)


def anticorrelated(key, n: int, d: int):
    """Points near the hyperplane sum(x) ~ d/2: good in one attribute
    means bad in another (the largest skylines).  The per-tuple plane
    offset has std 0.05; the zero-sum jitter spreads each tuple along
    its plane; values are reflected into [0, 1]."""
    kb, kj = jax.random.split(key)
    base = 0.5 + 0.05 * jax.random.normal(kb, (n, 1), jnp.float32)
    jit = jax.random.uniform(kj, (n, d), jnp.float32, -0.5, 0.5)
    jit = (jit - jnp.mean(jit, axis=-1, keepdims=True)) * 0.9
    x = jnp.abs(base + jit)
    x = 1.0 - jnp.abs(1.0 - x)
    return jnp.clip(x, 0.0, 1.0)


DISTRIBUTIONS = {"uniform": uniform, "anticorrelated": anticorrelated}


def seed_key(seed: int):
    """PRNG key of a whole-number seed of any size: the low and the high
    32 bits both enter the key (`PRNGKey` alone drops the high ones)."""
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


@functools.lru_cache(maxsize=None)
def _pool_fn(dist: str, count: int, n: int, d: int, sharding):
    gen = DISTRIBUTIONS[dist]

    def make(key):
        return tuple(gen(jax.random.fold_in(key, i), n, d)
                     for i in range(count))

    out = None if sharding is None else (sharding,) * count
    return jax.jit(make, out_shardings=out)


def make_pool(dist: str, seed: int, count: int, n: int, d: int,
              sharding=None) -> tuple:
    """``count`` tables of (n, d) float32, table i drawn from (seed, i),
    all made on the device in one jitted call (placed by ``sharding``
    where given)."""
    if dist not in DISTRIBUTIONS:
        raise ValueError(f"unknown distribution {dist!r}; "
                         f"one of {sorted(DISTRIBUTIONS)}")
    return _pool_fn(dist, count, n, d, sharding)(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _stack_fn(dist: str, count: int, n: int, d: int):
    gen = DISTRIBUTIONS[dist]
    return jax.jit(lambda key: jax.vmap(
        lambda i: gen(jax.random.fold_in(key, i), n, d))(jnp.arange(count)))


def make_stack(dist: str, seed: int, count: int, n: int, d: int):
    """The same ``count`` tables as `make_pool`, as one (count, n, d)
    array (one vmapped call: cheap to compile for large counts)."""
    if dist not in DISTRIBUTIONS:
        raise ValueError(f"unknown distribution {dist!r}; "
                         f"one of {sorted(DISTRIBUTIONS)}")
    return _stack_fn(dist, count, n, d)(seed_key(seed))
