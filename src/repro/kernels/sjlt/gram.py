"""Pallas TPU kernel: fused SJLT sketch→Gram — G = (SA)ᵀ(SA) in ONE pass over A.

The grid, accumulators and Gram steps are :mod:`repro.kernels.fused_gram`'s, as for
every family. This module supplies the S tile: S[i, j] = Σ_t sign[j, t]·[bucket[j, t]
= i], built by comparing each global row id with the s bucket ids of the tile's
columns (s compare-selects per entry; the counter-derived (bucket, sign) parameters
are the ones ``SJLTOp`` uses, so the fused Gram is the Gram of exactly that sketch).
The S tile then goes through the same MXU dot as the dense families — the old
one-hot (n·s, m) scatter-matmul and its (nb·s, 1) reshape, which Mosaic refuses,
are gone.

Parameters arrive transposed, (q, s, n_pad): a worker's bucket ids for one column
tile are s lane-dense rows. Padded input rows carry bucket −1 (matches no row) and
sign 0. VMEM at the chip smoke's shapes (d_pad=1024, m=10000, s=4): the shared
kernel's 12 MiB per worker and 5.25 MiB fixed, plus 64 KiB per worker of parameter blocks
— q=8 runs as two launches of 4 workers, 53.5 MiB each.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common, fused_gram


def param_bytes_per_worker(s: int) -> int:
    """VMEM of one worker's double-buffered (s, bn) bucket and sign blocks."""
    return 2 * 2 * 4 * common.round_up(s, 8) * fused_gram.BLOCK_N


def sjlt_gram_tiles(
    A: jax.Array,
    buckets: jax.Array,
    signs: jax.Array,
    m: int,
    p: fused_gram.Plan,
    *,
    interpret: bool = True,
) -> jax.Array:
    """All q workers' SJLT Grams from one launch. ``A``: (n_pad, d_pad);
    ``buckets``/``signs``: (q, s, n_pad) int32 / f32. Returns (q, d_pad, d_pad) f32;
    worker w is bitwise equal to a one-worker launch with its parameters."""
    q, s, _ = buckets.shape
    spec = pl.BlockSpec((q, s, p.bn), lambda mb, ni: (0, 0, ni))

    def s_tile(refs, w, r0, rl, c0):
        b_ref, s_ref = refs
        rows = r0 + jax.lax.broadcasted_iota(jnp.int32, (p.gen, p.bn), 0)
        bk = b_ref[w]
        sg = s_ref[w]
        tile = jnp.zeros((p.gen, p.bn), jnp.float32)
        for t in range(s):  # s nonzeros per column
            tile = tile + jnp.where(rows == bk[t : t + 1, :], sg[t : t + 1, :], 0.0)
        return tile

    return fused_gram.gram_multi(
        A,
        [(buckets, spec), (signs, spec)],
        s_tile,
        q,
        p,
        name="sjlt_gram",
        interpret=interpret,
    )
