"""Pallas TPU kernels: fused Gaussian sketch→Gram, and the Gaussian adjoint.

The gram kernel never materializes S or SA: :mod:`repro.kernels.fused_gram` walks
row tiles of A over an m-blocked grid, and this module supplies the S tile —
i.i.d. N(0, 1/m) from the counter RNG (``common.counter_normal`` at global
(row, column)), the same stream as ``GaussianOp.columns`` and the apply kernel.
Per entry that is one 20-round threefry plus Box-Muller on the VPU against 2·d
MXU flops. Sketching ``[A | b]`` jointly yields G and c from the same pass.

VMEM at the chip smoke's shapes (d_pad=1024, m=10000): the shared kernel's figures
(12 MiB per worker, 5.25 MiB fixed; the (q, 2) key words sit in SMEM) — q=8
runs as two launches of 4 workers, 53.25 MiB each.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common, fused_gram


def gaussian_gram_tiles(
    A: jax.Array,
    key_words: jax.Array,
    m: int,
    p: fused_gram.Plan,
    *,
    interpret: bool = True,
) -> jax.Array:
    """All q workers' Grams ``(S_w A)ᵀ(S_w A)`` with S ~ N(0, 1/m) made in-core.

    ``A``: (n_pad, d_pad) zero-padded; ``key_words``: (q, 2) uint32, one counter key
    per worker. Returns (q, d_pad, d_pad) f32; worker w is bitwise equal to a
    one-worker launch with ``key_words[w:w+1]``.
    """
    q = key_words.shape[0]
    inv_sqrt_m = 1.0 / math.sqrt(m)

    def s_tile(refs, w, r0, rl, c0):
        (kw_ref,) = refs
        rows, live = fused_gram.row_mask(r0, p.gen, p.bn, m)
        cols = jnp.asarray(c0).astype(jnp.uint32) + jax.lax.broadcasted_iota(
            jnp.uint32, (p.gen, p.bn), 1
        )
        z = common.counter_normal(kw_ref[w, 0], kw_ref[w, 1], rows, cols) * jnp.float32(inv_sqrt_m)
        return jnp.where(live, z, 0.0)

    return fused_gram.gram_multi(
        A,
        [(key_words, pl.BlockSpec(memory_space=pltpu.SMEM))],
        s_tile,
        q,
        p,
        name="gaussian_gram",
        interpret=interpret,
    )


def gaussian_adjoint_tiles(
    Y: jax.Array,
    key_words: jax.Array,
    n_pad: int,
    *,
    block_n: int,
    block_m: int,
    block_k: int,
    inv_sqrt_m: float,
    interpret: bool = True,
) -> jax.Array:
    """out = Sᵀ @ Y with S generated in-core (the missing Gaussian adjoint kernel).

    Y: (m_pad, k_pad), zero-padded below the true m so padded sketch rows contribute
    nothing. Grid (n_tiles, k_tiles, m_tiles) with m innermost: the (block_n, block_k)
    output tile is revisited and accumulated across m steps, exactly mirroring the
    forward kernel's n-accumulation. S tiles use the same (key, i, j) counter stream
    as the forward pass, so adjoint(apply(x)) sees one consistent S.
    """
    m, k = Y.shape
    grid = (n_pad // block_n, k // block_k, m // block_m)

    def kernel(kw_ref, y_ref, o_ref):
        ni = pl.program_id(0)
        mi = pl.program_id(2)

        @pl.when(mi == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        k0 = kw_ref[0]
        k1 = kw_ref[1]
        rows = (mi * block_m).astype(jnp.uint32) + jax.lax.broadcasted_iota(
            jnp.uint32, (block_m, block_n), 0
        )
        cols = (ni * block_n).astype(jnp.uint32) + jax.lax.broadcasted_iota(
            jnp.uint32, (block_m, block_n), 1
        )
        s_tile = common.counter_normal(k0, k1, rows, cols) * jnp.float32(inv_sqrt_m)
        contrib = jax.lax.dot_general(
            s_tile, y_ref[...], (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        o_ref[...] += contrib

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((2,), lambda ni, ki, mi: (0,)),
            pl.BlockSpec((block_m, block_k), lambda ni, ki, mi: (mi, ki)),
        ],
        out_specs=pl.BlockSpec((block_n, block_k), lambda ni, ki, mi: (ni, ki)),
        out_shape=jax.ShapeDtypeStruct((n_pad, k), jnp.float32),
        interpret=interpret,
    )(key_words, Y)
