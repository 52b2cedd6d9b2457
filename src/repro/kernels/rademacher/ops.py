"""Public packed-sign Rademacher ops: the cheap-RNG dense sketch family."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import common, fused_gram
from repro.kernels.rademacher import gram as K_gram
from repro.kernels.rademacher import kernel as K

BLOCK_M = 256
BLOCK_N = 512
BLOCK_D = 256


def _block_n(n: int) -> int:
    # One threefry word covers 32 columns, so the row-tile width must be a
    # multiple of 32 (zero-pad A up to it; zero rows contribute nothing).
    return min(BLOCK_N, common.round_up(n, 32))


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
def rademacher_sketch(
    key: jax.Array, A: jax.Array, m: int, *, interpret: bool | None = None
) -> jax.Array:
    """S @ A with S = ±1/√m generated in-core (1 threefry per 32 entries)."""
    interpret = common.resolve_interpret(interpret)
    orig_ndim = A.ndim
    if A.ndim == 1:
        A = A[:, None]
    n, d = A.shape
    dtype = A.dtype

    bm = min(BLOCK_M, common.round_up(m, 8))
    bn = _block_n(n)
    bd = min(BLOCK_D, common.round_up(d, 128))
    m_pad = common.round_up(m, bm)
    n_pad = common.round_up(n, bn)
    d_pad = common.round_up(d, bd)

    Af = common.pad_axis_to(common.pad_axis_to(A.astype(jnp.float32), 0, n_pad), 1, d_pad)
    k0, k1 = common.key_to_words(key)
    key_words = jnp.stack([k0, k1])

    out = K.rademacher_tiles(
        Af,
        key_words,
        m_pad,
        block_m=bm,
        block_n=bn,
        block_d=bd,
        inv_sqrt_m=1.0 / math.sqrt(m),
        interpret=interpret,
    )
    out = out[:m, :d].astype(dtype)
    return out[:, 0] if orig_ndim == 1 else out


def rademacher_gram(
    key: jax.Array, A: jax.Array, m: int, *, interpret: bool | None = None
) -> jax.Array:
    """G = (SA)ᵀ(SA) ∈ R^{d×d} in one fused pass — S and SA never touch HBM.
    The one-worker case of :func:`rademacher_gram_multi`."""
    return rademacher_gram_multi(key[None], A, m, interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
def rademacher_gram_multi(
    keys: jax.Array, A: jax.Array, m: int, *, interpret: bool | None = None
) -> jax.Array:
    """All q workers' ``G_k`` from one read of A per launch. ``keys``: (q,) PRNG
    keys; returns (q, d, d), slice w bitwise == ``rademacher_gram(keys[w], A, m)``."""
    interpret = common.resolve_interpret(interpret)
    n, d = A.shape
    q = keys.shape[0]
    p = fused_gram.plan(q, m, n, d)
    Af = fused_gram.pad_data(A, p)
    key_words = common.keys_to_words(keys)
    G = fused_gram.chunked(
        lambda s, k: K_gram.rademacher_gram_tiles(
            Af, key_words[s : s + k], m, p, interpret=interpret
        ),
        q,
        p,
    )
    return G[:, :d, :d]

