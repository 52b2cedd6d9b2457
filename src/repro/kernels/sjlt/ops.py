"""Public SJLT ops: parameter generation + padded kernel dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common, fused_gram
from repro.kernels.sjlt import gram as K_gram
from repro.kernels.sjlt import kernel as K
from repro.kernels.sjlt import ref as R

BLOCK_M = 512
BLOCK_N = 256
BLOCK_D = 256


def sjlt_params(key: jax.Array, n: int, s: int, m: int, dtype=jnp.float32):
    """Bucket indices and ±1/√s signs — the (only) randomness of the sketch.

    Counter-derived per *global* row index (``common.sjlt_counter_params``), the
    identical draw ``repro.core.operators.SJLTOp`` uses, so the kernel and the
    pure-jnp path see the same S for the same key — and so any row block's
    parameters can be regenerated independently when streaming.
    """
    k0, k1 = common.key_to_words(key)
    return common.sjlt_counter_params(k0, k1, jnp.arange(n), s, m, dtype=dtype)


@functools.partial(jax.jit, static_argnames=("m", "interpret", "use_ref"))
def sjlt_apply(
    A: jax.Array,
    buckets: jax.Array,
    signs: jax.Array,
    m: int,
    *,
    interpret: bool | None = None,
    use_ref: bool = False,
) -> jax.Array:
    """S @ A for the SJLT defined by (buckets, signs). A: (n, d) -> (m, d)."""
    interpret = common.resolve_interpret(interpret)
    if use_ref:
        return R.sjlt_apply(A, buckets, signs, m)
    n, d = A.shape
    s = buckets.shape[1]
    dtype = A.dtype

    bm = min(BLOCK_M, common.round_up(m, 128))
    bn = min(BLOCK_N, common.round_up(n, 8))
    bd = min(BLOCK_D, common.round_up(d, 128))
    m_pad = common.round_up(m, bm)
    n_pad = common.round_up(n, bn)
    d_pad = common.round_up(d, bd)

    Af = common.pad_axis_to(common.pad_axis_to(A.astype(jnp.float32), 0, n_pad), 1, d_pad)
    # Padded (fictitious) input rows must not contribute: route them to bucket -1,
    # which no m-tile's local iota can match.
    with jax.named_scope(common.SKETCH_PARAMS_SCOPE):
        buckets_p = common.pad_axis_to(buckets + 1, 0, n_pad) - 1
        signs_p = common.pad_axis_to(signs.astype(jnp.float32), 0, n_pad)

    out = K.sjlt_tiles(
        Af, buckets_p, signs_p, m_pad, block_m=bm, block_n=bn, block_d=bd, interpret=interpret
    )
    return out[:m, :d].astype(dtype)


def sjlt_gram(
    A: jax.Array,
    buckets: jax.Array,
    signs: jax.Array,
    m: int,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """G = (SA)ᵀ(SA) ∈ R^{d×d} in one fused pass over A (SA never hits HBM).
    The one-worker case of :func:`sjlt_gram_multi`."""
    return sjlt_gram_multi(A, buckets[None], signs[None], m, interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
def sjlt_gram_multi(
    A: jax.Array,
    buckets: jax.Array,
    signs: jax.Array,
    m: int,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """All q workers' ``G_k`` for per-worker SJLT params, one read of A per launch.

    ``buckets``/``signs``: (q, n, s). Returns (q, d, d) f32; worker slice w is
    bitwise-identical to ``sjlt_gram(A, buckets[w], signs[w], m)``.
    """
    interpret = common.resolve_interpret(interpret)
    n, d = A.shape
    q, _, s = buckets.shape
    p = fused_gram.plan(q, m, n, d, per_worker_bytes=K_gram.param_bytes_per_worker(s))
    Af = fused_gram.pad_data(A, p)
    # Padded (fictitious) rows: bucket -1 matches no sketch row, sign 0.
    with jax.named_scope(common.SKETCH_PARAMS_SCOPE):
        buckets_t = (common.pad_axis_to(buckets + 1, 1, p.n_pad) - 1).transpose(0, 2, 1)
        signs_t = common.pad_axis_to(signs.astype(jnp.float32), 1, p.n_pad).transpose(0, 2, 1)
    G = fused_gram.chunked(
        lambda s0, k: K_gram.sjlt_gram_tiles(
            Af, buckets_t[s0 : s0 + k], signs_t[s0 : s0 + k], m, p, interpret=interpret
        ),
        q,
        p,
    )
    return G[:, :d, :d]


def sjlt_sketch(
    key: jax.Array, A: jax.Array, m: int, *, s: int = 4, interpret: bool | None = None
) -> jax.Array:
    """Draw SJLT params from ``key`` and apply via the kernel."""
    buckets, signs = sjlt_params(key, A.shape[0], s, m, dtype=jnp.float32)
    return sjlt_apply(A, buckets, signs, m, interpret=interpret)

