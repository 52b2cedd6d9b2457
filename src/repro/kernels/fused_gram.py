"""One Pallas kernel body for every fused sketch→Gram family: ``G_w = (S_w A)ᵀ(S_w A)``
for q workers from one launch, with S generated in-core.

The sketch-and-solve hot loop only ever consumes ``S A`` through its Gram ``G`` (and
``c`` — callers sketch ``[A | b]`` jointly and slice). Each family (Gaussian,
Rademacher, SJLT, SRHT) differs only in how one tile of S is made; this module owns
everything else:

* **m-blocked grid** ``(m_blocks, n_tiles)``, n innermost. The whole ``(m, d)`` sketch
  never sits in VMEM (at m=1e4, d=1024 it is 41 MB per worker). Instead one
  ``(bm, d)`` row block of ``S_w A`` is accumulated over the n tiles, and at the last
  n tile its Gram is added to the resident output:
  ``G_w = Σ_blocks (S_w A)_blkᵀ (S_w A)_blk``. A is re-read once per m block, which
  costs far less than the 2·bm·d flops per A byte that each re-read buys.
* **row sub-chunks**: inside one grid step the ``(bm, bn)`` S tile is made ``gen``
  rows at a time into a ``(sub, bn)`` scratch that feeds one MXU dot, so the RNG
  code and its temporaries stay small at any bm (Mosaic unrolls vector code, and
  compile time grows with it) while the dot keeps 128-row passes.
* **q workers** share each A tile in a loop. Every worker runs the same op sequence
  over the same tile walk, and the blocking does not depend on q, so worker ``w``
  of a q-worker launch is bitwise equal to a one-worker launch with its key.
  When q workers' accumulators and Grams do not fit the VMEM budget, the ops
  split q into equal chunks (:func:`chunked`) — one launch per chunk.
* **precision**: every dot is f32 ``HIGHEST``. The Gram squares κ(SA), so the
  default (bf16-pass) contraction is not accurate enough for the solve.

VMEM at the chip smoke's shapes (d+1=1001 padded to 1024, m=10000, q=8, f32), as
:func:`plan` computes it: ``bm=1024`` (10 m blocks, 10240 padded rows), ``bn=512``,
``sub=128``, ``gen=32``. Per worker, a 4 MiB accumulator plus the double-buffered
(1024, 1024) Gram, 8 MiB: 12 MiB. Fixed: 4 MiB of double-buffered A tiles, the
0.25 MiB S scratch and 1 MiB allowed for RNG temporaries: 5.25 MiB. The 96 MiB
budget (of v5e's 128 MiB) fits 7 workers, so q=8 runs as 2 launches of 4 workers
at 53.25 MiB each, plus what the family adds (see each ``gram.py``); a one-worker
launch needs 17.25 MiB.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

MiB = 2**20
VMEM_BUDGET = 96 * MiB  # of the 128 MiB a v5e TensorCore has
ACC_BYTES = 4 * MiB  # cap on one worker's (bm, d) f32 accumulator
BLOCK_N = 512  # A rows per grid step; a multiple of 32 (packed Rademacher words)
SUB_ROWS = 128  # S rows per MXU dot: a full 128-row pass of the systolic array
GEN_ROWS = 32  # S rows per RNG step: keeps the unrolled RNG code (and compile) small
TEMP_TILES = 16  # live (gen, bn) f32 temporaries the RNG may keep in VMEM
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Plan:
    """Static blocking of one fused-gram call (all sizes in rows / bytes)."""

    q_chunk: int  # workers per launch
    bm: int  # S rows per m block (multiple of sub)
    sub: int  # S rows per MXU dot (and per Gram step)
    gen: int  # S rows made per RNG step; divides sub
    bn: int  # A rows per grid step
    m_pad: int
    n_pad: int
    d_pad: int
    vmem_bytes: int  # estimate for one launch of q_chunk workers

    @property
    def m_blocks(self) -> int:
        return self.m_pad // self.bm


def plan(
    q: int,
    m: int,
    n: int,
    d: int,
    *,
    per_worker_bytes: int = 0,
    per_row_bytes: int = 0,
) -> Plan:
    """Choose the blocking from the shape alone.

    ``bm``/``sub``/``bn`` depend on (m, n, d) only — never on q — which is what keeps a
    worker's result independent of how many workers share its launch.
    ``per_worker_bytes`` and ``per_row_bytes`` (per worker and S row of an m block)
    are the family's extra VMEM operands.
    Raises when not even one worker fits the budget (no silent fallback).
    """
    d_pad = common.round_up(d, 128)
    sub = min(SUB_ROWS, common.round_up(m, 8))
    gen = GEN_ROWS if sub % GEN_ROWS == 0 else sub
    bm_max = max(sub, (ACC_BYTES // (4 * d_pad)) // sub * sub)
    m_blocks = -(-m // bm_max)
    bm = common.round_up(-(-m // m_blocks), sub)
    bn = min(BLOCK_N, common.round_up(n, 32))
    n_pad = common.round_up(n, bn)
    fixed = 4 * (2 * bn * d_pad + sub * bn + TEMP_TILES * gen * bn)
    worker = 4 * (bm * d_pad + 2 * d_pad * d_pad) + per_worker_bytes + bm * per_row_bytes
    q_fit = (VMEM_BUDGET - fixed) // worker
    if q_fit < 1:
        raise ValueError(
            f"fused gram kernel: one worker needs {(fixed + worker) / MiB:.1f} MiB of VMEM at "
            f"m={m}, d_pad={d_pad} (budget {VMEM_BUDGET / MiB:.0f} MiB)"
        )
    chunks = -(-q // q_fit)
    q_chunk = -(-q // chunks)
    return Plan(
        q_chunk=q_chunk,
        bm=bm,
        sub=sub,
        gen=gen,
        bn=bn,
        m_pad=m_blocks * bm,
        n_pad=n_pad,
        d_pad=d_pad,
        vmem_bytes=fixed + q_chunk * worker,
    )


def pad_data(A: jax.Array, p: Plan) -> jax.Array:
    """f32 A zero-padded to (n_pad, d_pad); zero rows/columns add nothing to G."""
    with jax.named_scope(common.GRAM_INPUT_SCOPE):
        return common.pad_axis_to(common.pad_axis_to(A.astype(jnp.float32), 0, p.n_pad), 1, p.d_pad)


def gram_multi(
    A: jax.Array,
    operands: Sequence[tuple],
    s_tile: Callable,
    q: int,
    p: Plan,
    *,
    name: str,
    interpret: bool,
) -> jax.Array:
    """Launch the fused kernel for ``q`` workers; returns (q, d_pad, d_pad) f32.

    ``operands``: ``(array, BlockSpec)`` pairs whose index maps take ``(mb, ni)``.
    ``s_tile(refs, w, r0, rl, c0)`` returns worker ``w``'s ``(p.gen, p.bn)`` S tile
    for global rows ``[r0, r0+gen)`` (``rl``: the same rows' offset inside the m
    block) and global columns ``[c0, c0+bn)``; rows ≥ m must come back zero.
    """
    n_pad, d_pad = A.shape
    sub, gen, bm, bn = p.sub, p.gen, p.bm, p.bn
    k = len(operands)

    def kernel(*refs):
        op_refs, a_ref, o_ref = refs[:k], refs[k], refs[k + 1]
        acc_ref, s_ref = refs[k + 2], refs[k + 3]
        mb = pl.program_id(0)
        ni = pl.program_id(1)

        @pl.when((mb == 0) & (ni == 0))
        def _init_out():
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(ni == 0)
        def _init_acc():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def chunk(j, carry):  # j walks (worker, row chunk); one A tile serves all q
            w = j // (bm // sub)
            rl = pl.multiple_of((j % (bm // sub)) * sub, sub)

            def make(g, c):  # S rows are made `gen` at a time: small RNG code
                gl = pl.multiple_of(g * gen, gen)
                s_ref[pl.ds(gl, gen), :] = s_tile(op_refs, w, mb * bm + rl + gl, rl + gl, ni * bn)
                return c

            jax.lax.fori_loop(0, sub // gen, make, 0)
            acc_ref[w, pl.ds(rl, sub), :] += jnp.dot(
                s_ref[...], a_ref[...], precision=HIGHEST, preferred_element_type=jnp.float32
            )
            return carry

        jax.lax.fori_loop(0, q * (bm // sub), chunk, 0)

        @pl.when(ni == pl.num_programs(1) - 1)
        def _gram():
            def add(j, carry):  # this block's Gram, `sub` accumulator rows at a time
                w = j // (bm // sub)
                blk = acc_ref[w, pl.ds(pl.multiple_of((j % (bm // sub)) * sub, sub), sub), :]
                o_ref[w] += jax.lax.dot_general(
                    blk,
                    blk,
                    (((0,), (0,)), ((), ())),
                    precision=HIGHEST,
                    preferred_element_type=jnp.float32,
                )
                return carry

            jax.lax.fori_loop(0, q * (bm // sub), add, 0)

    return pl.pallas_call(
        kernel,
        grid=(p.m_blocks, n_pad // bn),
        in_specs=[spec for _, spec in operands]
        + [pl.BlockSpec((bn, d_pad), lambda mb, ni: (ni, 0))],
        out_specs=pl.BlockSpec((q, d_pad, d_pad), lambda mb, ni: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((q, d_pad, d_pad), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((q, bm, d_pad), jnp.float32),
            pltpu.VMEM((sub, bn), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(p.vmem_bytes + 8 * MiB, 120 * MiB),
        ),
        name=name,
        interpret=interpret,
    )(*[x for x, _ in operands], A)


def chunked(launch: Callable[[int, int], jax.Array], q: int, p: Plan) -> jax.Array:
    """Run ``launch(start, size)`` over q in chunks of ``p.q_chunk``; (q, ...) result."""
    outs = [launch(s, min(p.q_chunk, q - s)) for s in range(0, q, p.q_chunk)]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


def row_mask(r0, nrows: int, bn: int, m: int):
    """(rows, bn) uint32 global row ids and the ``rows < m`` mask."""
    rows = jnp.asarray(r0).astype(jnp.uint32) + jax.lax.broadcasted_iota(
        jnp.uint32, (nrows, bn), 0
    )
    return rows, rows < jnp.uint32(m)

