"""The paper's regression workloads, regenerated synthetically (offline container).

Each generator returns (A, b, meta). ``b`` may be (n,) or (n, k) (multi-target — the
EMNIST one-hot least squares). Heavy-tailed student-t data reproduces the Fig. 3
conditioning regime; ``airline_like`` mimics the dummy-coded categorical structure of
the paper's main dataset (mostly-sparse 0/1 features + a few numeric columns).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

T_BLOCK_ROWS = 2**14  # student-t rows drawn per block (bounds the sampler's temporaries)


def gaussian_regression(key, n: int, d: int, *, noise: float = 0.1, planted: bool = True):
    ka, kx, ke = jax.random.split(key, 3)
    A = jax.random.normal(ka, (n, d))
    if planted:
        x = jax.random.normal(kx, (d,))
        b = A @ x + noise * jax.random.normal(ke, (n,))
    else:
        b = jax.random.normal(ke, (n,))
        x = None
    return A, b, {"x_truth": x}


def student_t_regression(key, n: int, d: int, *, df: float = 1.5, noise: float = 0.1):
    """Paper Fig. 3: A entries ~ student-t(df) (heavy-tailed, high row-coherence).

    A is drawn in row blocks of ``min(n, T_BLOCK_ROWS)`` (block i from
    ``fold_in(key_A, i)``) so the t sampler's rejection-loop temporaries scale with a
    block, not with A: at n=2^20, d=1000 a one-shot draw needs ~76 GB of device memory.
    """
    ka, kx, ke = jax.random.split(key, 3)
    rows = min(n, T_BLOCK_ROWS)
    nb = -(-n // rows)

    def draw(i):
        # clip the extreme tail so f(x*) is finite-variance enough for Monte Carlo runs
        return jnp.clip(jax.random.t(jax.random.fold_in(ka, i), df, (rows, d)), -1e3, 1e3)

    A = jax.lax.map(draw, jnp.arange(nb)).reshape(nb * rows, d)[:n]
    x = jax.random.normal(kx, (d,))
    b = A @ x + noise * jax.random.normal(ke, (n,))
    return A, b, {"x_truth": x}


def airline_like(key, n: int, *, cards=(12, 31, 7, 24, 60), numeric: int = 2, noise: float = 0.3):
    """Dummy-coded categorical design like the paper's airline matrix.

    ``cards`` are category cardinalities (month, day-of-month, day-of-week, hour, ...);
    each contributes a one-hot block. d = sum(cards) + numeric. The planted output is
    a logit-ish linear response thresholded to {0,1} (the DepDelay>15 target).
    """
    keys = jax.random.split(key, len(cards) + 3)
    blocks = []
    for i, c in enumerate(cards):
        idx = jax.random.randint(keys[i], (n,), 0, c)
        blocks.append(jax.nn.one_hot(idx, c, dtype=jnp.float32))
    num = jax.random.lognormal(keys[-3], shape=(n, numeric)) / 5.0  # distance-ish
    A = jnp.concatenate(blocks + [num], axis=1)
    d = A.shape[1]
    x = jax.random.normal(keys[-2], (d,)) / math.sqrt(d)
    score = A @ x + noise * jax.random.normal(keys[-1], (n,))
    b = (score > jnp.median(score)).astype(jnp.float32)
    return A, b, {"x_truth": x, "d": d}


def emnist_like(key, n: int, *, classes: int = 47, img_dim: int = 784, noise: float = 1.0):
    """Class-structured image-like data for the Fig. 2 experiment: rows are noisy
    class templates, b is the one-hot label matrix (least squares as multiclass).

    Class frequencies are Zipf-skewed and template norms vary ~8× — real EMNIST rows
    have very uneven leverage (that is *why* the paper's Fig. 2 shows SJLT beating
    uniform sampling); an i.i.d.-homogeneous stand-in would hide the effect."""
    kt, kl, ke, ks = jax.random.split(key, 4)
    templates = jax.random.normal(kt, (classes, img_dim)) * 2.0
    scale = jnp.exp(jnp.linspace(jnp.log(0.5), jnp.log(4.0), classes))
    templates = templates * scale[:, None]
    probs = 1.0 / (1.0 + jnp.arange(classes, dtype=jnp.float32))
    labels = jax.random.categorical(kl, jnp.log(probs / probs.sum()), shape=(n,))
    A = templates[labels] + noise * jax.random.normal(ke, (n, img_dim))
    B = jax.nn.one_hot(labels, classes, dtype=jnp.float32)
    return A, B, {"labels": labels}


def accuracy(A, B_onehot, X, labels) -> jax.Array:
    """Multiclass accuracy of the least-squares classifier X (img_dim, classes)."""
    pred = jnp.argmax(A @ X, axis=1)
    return jnp.mean((pred == labels).astype(jnp.float32))
