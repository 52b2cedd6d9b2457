"""Pallas TPU kernel: fused SRHT sketch→Gram — G = (SA)ᵀ(SA) in ONE pass over A.

The FWHT formulation of the SRHT needs the whole (padded) column dimension resident
before any output row is final — it cannot stream row tiles of A. The streaming form
instead makes S *tiles* directly from the Sylvester closed form

    S[r, j] = (1/√m) · (−1)^popcount(rows[r] & j) · D[j]

(an AND + popcount per element — no transform, no HBM traffic for S) and runs on
:mod:`repro.kernels.fused_gram`'s grid like every other family. For the paper's
m = O(d) ≪ n regime both forms are dominated by the matmul with A, and only this one
never needs all of A at once.

The sampled-row ids arrive as (q, m_pad, 1) int32 padded with −1 (masked in-kernel),
one (bm, 1) column block per m block. VMEM at the chip smoke's shapes (d_pad=1024,
m=10000): the shared kernel's 12 MiB per worker and 5.25 MiB fixed, plus 1 MiB per worker
for the lane-padded row-id blocks — q=8 runs as two launches of 4 workers,
57.25 MiB each.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common, fused_gram

# A (bm, 1) int32 block is lane-padded to (bm, 128) in VMEM, and double-buffered.
ROW_ID_BYTES = 2 * 4 * 128


def srht_gram_tiles(
    A: jax.Array,
    rows: jax.Array,
    key_words: jax.Array,
    m: int,
    p: fused_gram.Plan,
    *,
    interpret: bool = True,
) -> jax.Array:
    """All q workers' SRHT Grams from one launch. ``A``: (n_pad, d_pad) zero-padded;
    ``rows``: (q, m_pad, 1) int32 sampled Hadamard rows, −1 padding; ``key_words``:
    (q, 2) uint32 Rademacher-diagonal keys. Returns (q, d_pad, d_pad) f32; worker w
    is bitwise equal to a one-worker launch with its rows and key."""
    q = key_words.shape[0]
    inv_sqrt_m = 1.0 / math.sqrt(m)

    def s_tile(refs, w, r0, rl, c0):
        kw_ref, r_ref = refs
        r = r_ref[w, pl.ds(rl, p.gen), :]  # (gen, 1), −1 marks padding
        j = jnp.asarray(c0).astype(jnp.uint32) + jax.lax.broadcasted_iota(
            jnp.uint32, (1, p.bn), 1
        )
        parity = jax.lax.population_count(r.astype(jnp.uint32) & j)  # (gen, bn)
        h = (1 - 2 * (parity & jnp.uint32(1)).astype(jnp.int32)).astype(jnp.float32)
        dsign = common.counter_rademacher(kw_ref[w, 0], kw_ref[w, 1], j, jnp.uint32(0))
        return jnp.where(r >= 0, h * dsign * jnp.float32(inv_sqrt_m), 0.0)

    return fused_gram.gram_multi(
        A,
        [
            (key_words, pl.BlockSpec(memory_space=pltpu.SMEM)),
            (rows, pl.BlockSpec((q, p.bm, 1), lambda mb, ni: (0, mb, 0))),
        ],
        s_tile,
        q,
        p,
        name="srht_gram",
        interpret=interpret,
    )
