"""Public FWHT op: arbitrary power-of-two n via two Kronecker grid passes.

    H_n = H_{n1} ⊗ H_{tile}                (n = n1 · tile)

Pass 1 applies H_tile within each contiguous tile of rows (one kernel tile each).
Pass 2 views the result as (n1, tile·d) — each *column* of that view is a stride-tile
slice — and applies H_{n1} across tiles with the same kernel. Between the passes the
data never needs a physical transpose: the reshape is contiguous because pass-2 rows
are exactly the pass-1 tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common, fused_gram
from repro.kernels.fwht import gram as K_gram
from repro.kernels.fwht import kernel as K

MAX_TILE_ROWS = 4096  # 4096×256 f32 FWHT tile = 4 MiB of VMEM
DEFAULT_BLOCK_D = 256


def _hadamard_factors(rows: int, dtype):
    k = min(128, rows)
    b = rows // k
    return common.hadamard_matrix(b, dtype), common.hadamard_matrix(k, dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fwht(x: jax.Array, *, block_d: int = DEFAULT_BLOCK_D, interpret: bool | None = None) -> jax.Array:
    """Unnormalized Walsh-Hadamard transform along axis 0 of x: (n, d), n pow2."""
    interpret = common.resolve_interpret(interpret)
    orig_ndim = x.ndim
    if x.ndim == 1:
        x = x[:, None]
    n, d = x.shape
    if n & (n - 1):
        raise ValueError(f"FWHT needs power-of-two n, got {n}")
    dtype = x.dtype
    xf = x.astype(jnp.float32)

    bd = min(block_d, max(128, d))
    d_pad = common.round_up(d, bd)
    xf = common.pad_axis_to(xf, 1, d_pad)

    tile = min(n, MAX_TILE_ROWS)
    n1 = n // tile

    ho, hi = _hadamard_factors(tile, jnp.float32)
    y = K.fwht_tiles(xf, ho, hi, tile_rows=tile, block_d=bd, interpret=interpret)

    if n1 > 1:
        # Pass 2: rows of the (n1, tile*d_pad) view are the pass-1 tiles.
        y2 = y.reshape(n1, tile * d_pad)
        bd2 = 512 if (tile * d_pad) % 512 == 0 else bd
        ho2, hi2 = _hadamard_factors(n1, jnp.float32)
        y2 = K.fwht_tiles(y2, ho2, hi2, tile_rows=n1, block_d=bd2, interpret=interpret)
        y = y2.reshape(n, d_pad)

    return y[:, :d].astype(dtype) if orig_ndim == 2 else y[:, 0].astype(dtype)


def srht_gram(
    A: jax.Array, rows: jax.Array, key_words: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    """G = (SA)ᵀ(SA) for the SRHT in one fused streamed pass (no FWHT, no SA in HBM).

    ``A``: (n, d), not sign-flipped — the Rademacher diagonal D keyed by
    ``key_words`` is applied inside the kernel via the Sylvester closed form.
    ``rows``: (m,) sampled Hadamard row ids. Returns (d, d) f32. The one-worker
    case of :func:`srht_gram_multi`.
    """
    return srht_gram_multi(A, rows[None], key_words[None], interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def srht_gram_multi(
    A: jax.Array, rows: jax.Array, key_words: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    """All q workers' SRHT Grams, one read of A per launch.

    ``rows``: (q, m) per-worker sampled Hadamard rows; ``key_words``: (q, 2)
    diagonal keys. Returns (q, d, d) f32, slice w bitwise-identical to
    ``srht_gram(A, rows[w], key_words[w])``.
    """
    interpret = common.resolve_interpret(interpret)
    n, d = A.shape
    q, m = rows.shape
    p = fused_gram.plan(q, m, n, d, per_row_bytes=K_gram.ROW_ID_BYTES)
    Af = fused_gram.pad_data(A, p)
    with jax.named_scope(common.SKETCH_PARAMS_SCOPE):
        rows_p = (common.pad_axis_to(rows.astype(jnp.int32) + 1, 1, p.m_pad) - 1)[..., None]
    G = fused_gram.chunked(
        lambda s, k: K_gram.srht_gram_tiles(
            Af, rows_p[s : s + k], key_words[s : s + k], m, p, interpret=interpret
        ),
        q,
        p,
    )
    return G[:, :d, :d]

