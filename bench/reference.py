"""The plain reference that decides ``correct``. Imports nothing of the program.

* :func:`lstsq` — x* = argmin ‖Ax − b‖² by dense least squares at f32 "highest"
  matmul precision (copied from the chip smoke's reference).
* :func:`cost` — f(x) = ‖Ax − b‖² (Frobenius for several targets), with Ax at f32
  "highest": the excess cost is a small difference of large terms.
* :func:`theorem1` — paper Theorem 1, E[(f(x̄) − f*)/f*] = d / (q(m − d − 1)).
* :func:`sketch_solve` — Algorithm 1 written plainly: q sketches, S·[A | b]
  accumulated over row blocks, each worker's normal equations solved, the q answers
  averaged. Gaussian S comes from ``jax.random``. SJLT S is the one the program
  draws for the same key: worker w's key is ``fold_in(fold_in(key, 0), w)``, and
  column j's s buckets and signs come from Threefry-2x32 of (key words; j, t),
  copied below from ``repro.kernels.common``. At ``precision="highest"`` it gives
  the x̄ that an SJLT answer is compared with; at ``"bf16"`` (one bf16 pass, f32
  accumulation) it is the control that the check must refuse.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK_ROWS = 2**14  # rows of A per block of the plain sketch


@jax.jit
def lstsq(A, b):
    with jax.default_matmul_precision("highest"):
        return jnp.linalg.lstsq(A, b)[0]


@jax.jit
def cost(A, b, x):
    r = jnp.matmul(A, x, precision=HIGHEST) - b
    return jnp.vdot(r, r).real


def theorem1(d: int, q: int, m: int) -> float:
    return d / (q * (m - d - 1))


_PARITY = np.uint32(0x1BD11BDA)
_ROT = (13, 15, 26, 6, 17, 29, 16, 24)


def threefry2x32(k0, k1, c0, c1):
    """Threefry-2x32, 20 rounds, on uint32 words (broadcastable)."""
    ks = (k0, k1, k0 ^ k1 ^ _PARITY)
    x0, x1 = c0 + ks[0], c1 + ks[1]
    for block in range(5):
        for r in range(4):
            x0 = x0 + x1
            rot = _ROT[(block % 2) * 4 + r]
            x1 = (x1 << np.uint32(rot)) | (x1 >> np.uint32(32 - rot))
            x1 = x1 ^ x0
        x0 = x0 + ks[(block + 1) % 3]
        x1 = x1 + ks[(block + 2) % 3] + np.uint32(block + 1)
    return x0, x1


def _s_block(family: str, words, i, m: int, rows: int, s: int):
    """(m, rows) f32 columns of one worker's S for row block i of A."""
    if family == "gaussian":
        key = jax.random.fold_in(jax.random.wrap_key_data(words, impl="threefry2x32"), i)
        return jax.random.normal(key, (m, rows)) * jnp.float32(1.0 / math.sqrt(m))
    if family == "sjlt":  # column j: s signed nonzeros at hashed rows (with replacement)
        cols = (i * rows + jnp.arange(rows)).astype(jnp.uint32)[None, :]
        b0, b1 = threefry2x32(words[0], words[1], cols, jnp.arange(s, dtype=jnp.uint32)[:, None])
        buckets = (b0 % jnp.uint32(m)).astype(jnp.int32)
        signs = (1 - 2 * (b1 & jnp.uint32(1)).astype(jnp.int32)).astype(jnp.float32) * jnp.float32(1.0 / np.sqrt(s))
        ids = jnp.arange(m)[:, None]
        return sum(jnp.where(ids == buckets[t][None, :], signs[t][None, :], 0.0) for t in range(s))
    raise ValueError(f"no plain sketch for family {family!r}")


def _dot(x, y, precision: str):
    if precision == "bf16":
        return jnp.matmul(x.astype(jnp.bfloat16), y.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    return jnp.matmul(x, y, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("family", "m", "q", "s", "precision"))
def sketch_solve(key, A, b, *, family: str, m: int, q: int, s: int = 0, precision: str = "highest"):
    """x̄ of q plain sketch-and-solve workers over (A, b); b may be (n,) or (n, k)."""
    n, d = A.shape
    Ab = jnp.concatenate([A, b.reshape(n, -1)], axis=1)
    rows = min(n, BLOCK_ROWS)
    nb = -(-n // rows)
    Ab = jnp.pad(Ab, ((0, nb * rows - n), (0, 0))).reshape(nb, rows, -1)

    round0 = jax.random.fold_in(key, 0)
    words = jax.vmap(lambda w: jax.random.key_data(jax.random.fold_in(round0, w)))(jnp.arange(q)).astype(jnp.uint32)

    def worker(w):
        def step(acc, i):
            S = _s_block(family, words[w], i, m, rows, s)  # padded rows of Ab are zero
            return acc + _dot(S, Ab[i], precision), None

        SAb, _ = jax.lax.scan(step, jnp.zeros((m, Ab.shape[2]), jnp.float32), jnp.arange(nb))
        G = _dot(SAb.T, SAb, precision)
        L = jnp.linalg.cholesky(G[:d, :d])
        y = jax.scipy.linalg.solve_triangular(L, G[:d, d:], lower=True)
        return jax.scipy.linalg.solve_triangular(L.T, y, lower=False)

    xs = jax.lax.map(worker, jnp.arange(q))
    return jnp.mean(xs, axis=0).reshape((d,) + b.shape[1:])
