"""The benchmark's own copies of the paper's regression generators.

Frozen from ``repro.data.regression`` (``student_t_regression`` and ``emnist_like``)
so that a change to the program cannot move the yardstick's inputs;
``bench/tests/test_frozen_copies.py`` shows that each copy still gives today's
``repro.data`` output bitwise at a small seed. Everything here is plain JAX.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

T_BLOCK_ROWS = 2**14  # student-t rows drawn per block (bounds the sampler's temporaries)


def student_t(key, n: int, d: int, *, df: float = 1.5, noise: float = 0.1):
    """Paper Fig. 3: A entries ~ student-t(df), clipped to ±1e3, b = A x + noise.

    A is drawn in row blocks of ``min(n, T_BLOCK_ROWS)`` rows (block i from
    ``fold_in(key_A, i)``): a one-shot draw at n=2^20, d=1000 needs ~76 GB.
    """
    ka, kx, ke = jax.random.split(key, 3)
    rows = min(n, T_BLOCK_ROWS)
    nb = -(-n // rows)

    def draw(i):
        return jnp.clip(jax.random.t(jax.random.fold_in(ka, i), df, (rows, d)), -1e3, 1e3)

    A = jax.lax.map(draw, jnp.arange(nb)).reshape(nb * rows, d)[:n]
    x = jax.random.normal(kx, (d,))
    b = A @ x + noise * jax.random.normal(ke, (n,))
    return A, b


def emnist_like(key, n: int, *, classes: int = 47, img_dim: int = 784, noise: float = 1.0):
    """Paper Fig. 2 stand-in: rows are noisy class templates (Zipf-skewed class
    frequencies, template norms spread ~8x), B is the (n, classes) one-hot label matrix."""
    kt, kl, ke, ks = jax.random.split(key, 4)
    templates = jax.random.normal(kt, (classes, img_dim)) * 2.0
    scale = jnp.exp(jnp.linspace(jnp.log(0.5), jnp.log(4.0), classes))
    templates = templates * scale[:, None]
    probs = 1.0 / (1.0 + jnp.arange(classes, dtype=jnp.float32))
    labels = jax.random.categorical(kl, jnp.log(probs / probs.sum()), shape=(n,))
    A = templates[labels] + noise * jax.random.normal(ke, (n, img_dim))
    B = jax.nn.one_hot(labels, classes, dtype=jnp.float32)
    return A, B


def make(config: dict, key, n: int):
    """(A, b) of a configuration at n rows; jit it to build them on the device."""
    kind = config["data"]
    if kind == "student_t":
        return student_t(key, n, config["d"], df=config["df"], noise=config["noise"])
    if kind == "emnist_like":
        return emnist_like(key, n, classes=config["targets"], img_dim=config["d"], noise=config["noise"])
    raise ValueError(f"unknown data kind {kind!r}")
