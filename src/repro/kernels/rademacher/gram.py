"""Pallas TPU kernels: fused Rademacher sketch→Gram — the cheap-RNG dense family.

The Gaussian gram kernel is RNG-bound: every S entry costs one 20-round threefry
*plus* Box-Muller (log/sqrt/cos). A Rademacher sketch S[i,j] = ±1/√m is also
sub-gaussian (it satisfies the same JL/embedding moment bounds the paper's Thm-1
averaging analysis needs — see "Distributed Hybrid Sketching for ℓ2-Embeddings",
arXiv:2412.20301), but its randomness is ONE BIT per entry: one threefry call
yields 32 packed signs (``common.packed_sign_words``), a ~64× reduction in RNG
uint work and the complete removal of the transcendental pipeline.

The grid, accumulators and Gram steps are :mod:`repro.kernels.fused_gram`'s, as
for every family; only the S-tile generator differs: words → bit-unpack → ±1,
instead of threefry → Box-Muller. VMEM at the chip smoke's shapes (d_pad=1024,
m=10000) is the shared kernel's: 12 MiB per worker, 5.25 MiB fixed, key words in SMEM
— q=8 runs as two launches of 4 workers, 53.25 MiB each.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common, fused_gram


def rademacher_gram_tiles(
    A: jax.Array,
    key_words: jax.Array,
    m: int,
    p: fused_gram.Plan,
    *,
    interpret: bool = True,
) -> jax.Array:
    """All q workers' Grams with S = ±1/√m made in-core from packed sign words.

    ``A``: (n_pad, d_pad) zero-filled, ``p.bn`` a multiple of 32 (one threefry word
    per 32 columns); ``key_words``: (q, 2) uint32. Returns (q, d_pad, d_pad) f32;
    worker w is bitwise equal to a one-worker launch with ``key_words[w:w+1]``.
    """
    q = key_words.shape[0]
    inv_sqrt_m = 1.0 / math.sqrt(m)

    def s_tile(refs, w, r0, rl, c0):
        (kw_ref,) = refs
        _, live = fused_gram.row_mask(r0, p.gen, p.bn, m)
        signs = common.packed_sign_tile(
            kw_ref[w, 0], kw_ref[w, 1], jnp.asarray(r0).astype(jnp.uint32),
            jnp.asarray(c0).astype(jnp.uint32), p.gen, p.bn,
        )
        return jnp.where(live, signs * jnp.float32(inv_sqrt_m), 0.0)

    return fused_gram.gram_multi(
        A,
        [(key_words, pl.BlockSpec(memory_space=pltpu.SMEM))],
        s_tile,
        q,
        p,
        name="rademacher_gram",
        interpret=interpret,
    )
