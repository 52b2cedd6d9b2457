"""Public RNG-fused Gaussian sketch op."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import common, fused_gram
from repro.kernels.gaussian import gram as K_gram
from repro.kernels.gaussian import kernel as K

BLOCK_M = 256
BLOCK_N = 512
BLOCK_D = 256


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
def gaussian_sketch(key: jax.Array, A: jax.Array, m: int, *, interpret: bool | None = None) -> jax.Array:
    """S @ A with S ~ N(0, 1/m)^{m×n} generated inside the kernel. A: (n, d)."""
    interpret = common.resolve_interpret(interpret)
    orig_ndim = A.ndim
    if A.ndim == 1:
        A = A[:, None]
    n, d = A.shape
    dtype = A.dtype

    bm = min(BLOCK_M, common.round_up(m, 8))
    bn = min(BLOCK_N, common.round_up(n, 8))
    bd = min(BLOCK_D, common.round_up(d, 128))
    m_pad = common.round_up(m, bm)
    n_pad = common.round_up(n, bn)
    d_pad = common.round_up(d, bd)

    Af = common.pad_axis_to(common.pad_axis_to(A.astype(jnp.float32), 0, n_pad), 1, d_pad)
    k0, k1 = common.key_to_words(key)
    key_words = jnp.stack([k0, k1])

    out = K.gaussian_tiles(
        Af,
        key_words,
        m_pad,
        n,
        block_m=bm,
        block_n=bn,
        block_d=bd,
        inv_sqrt_m=1.0 / math.sqrt(m),
        interpret=interpret,
    )
    out = out[:m, :d].astype(dtype)
    return out[:, 0] if orig_ndim == 1 else out


def gaussian_gram(key: jax.Array, A: jax.Array, m: int, *, interpret: bool | None = None) -> jax.Array:
    """G = (SA)ᵀ(SA) ∈ R^{d×d} in one fused pass — S and SA never touch HBM.

    Pass ``A = [data | b]`` to get the Gram and right-hand side of the sketched
    normal equations from a single streaming of the data (callers slice G and c).
    The one-worker case of :func:`gaussian_gram_multi`.
    """
    return gaussian_gram_multi(key[None], A, m, interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
def gaussian_gram_multi(
    keys: jax.Array, A: jax.Array, m: int, *, interpret: bool | None = None
) -> jax.Array:
    """All q workers' ``G_k = (S_kA)ᵀ(S_kA)`` from one read of A per launch.

    ``keys``: (q,)-batched PRNG keys (``prng.worker_keys``). Returns (q, d, d)
    f32, worker slice w bitwise-identical to ``gaussian_gram(keys[w], A, m)``.
    q is split into launches only when the VMEM budget demands it
    (:func:`repro.kernels.fused_gram.plan`).
    """
    interpret = common.resolve_interpret(interpret)
    n, d = A.shape
    q = keys.shape[0]
    p = fused_gram.plan(q, m, n, d)
    Af = fused_gram.pad_data(A, p)
    key_words = common.keys_to_words(keys)
    G = fused_gram.chunked(
        lambda s, k: K_gram.gaussian_gram_tiles(
            Af, key_words[s : s + k], m, p, interpret=interpret
        ),
        q,
        p,
    )
    return G[:, :d, :d]


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def gaussian_adjoint(key: jax.Array, Y: jax.Array, n: int, *, interpret: bool | None = None) -> jax.Array:
    """Sᵀ @ Y with S ~ N(0, 1/m)^{m×n} regenerated in-core. Y: (m, k) or (m,)."""
    interpret = common.resolve_interpret(interpret)
    orig_ndim = Y.ndim
    if Y.ndim == 1:
        Y = Y[:, None]
    m, k = Y.shape
    dtype = Y.dtype

    bm = min(BLOCK_M, common.round_up(m, 8))
    bn = min(BLOCK_N, common.round_up(n, 8))
    bk = min(BLOCK_D, common.round_up(k, 128))
    m_pad = common.round_up(m, bm)
    n_pad = common.round_up(n, bn)
    k_pad = common.round_up(k, bk)

    Yf = common.pad_axis_to(common.pad_axis_to(Y.astype(jnp.float32), 0, m_pad), 1, k_pad)
    k0, k1 = common.key_to_words(key)
    key_words = jnp.stack([k0, k1])

    out = K_gram.gaussian_adjoint_tiles(
        Yf,
        key_words,
        n_pad,
        block_n=bn,
        block_m=bm,
        block_k=bk,
        inv_sqrt_m=1.0 / math.sqrt(m),
        interpret=interpret,
    )
    out = out[:n, :k].astype(dtype)
    return out[:, 0] if orig_ndim == 1 else out

