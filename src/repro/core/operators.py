"""`SketchOp`: the paper's sketch family as composable linear operators.

Every sketch ``S ∈ R^{m×n}`` in the repo used to exist only as a *function*
``(key, A) -> S @ A`` dispatched through a string-keyed if-chain. This module turns
each kind into a frozen linear-operator object built once from ``(SketchSpec, key, n)``
and exposing the full operator calculus the pipeline needs:

  * ``apply(A)``                 — ``S @ A`` (fast path per kind; Pallas kernel when
                                   ``spec.use_kernel`` and one exists),
  * ``adjoint(Y)``               — ``Sᵀ @ Y`` without ever materializing S (scatter for
                                   sampling sketches, FWHT for SRHT, gather for SJLT,
                                   streamed counter-RNG tiles for Gaussian),
  * ``apply_blocked(A, block_rows=...)`` — a ``lax.scan`` over row tiles of A, so ``n``
                                   can exceed device memory: each sketch is a sum /
                                   gather over row blocks and tile ``(i, j)`` of the
                                   random S is a pure function of ``(key, i, j)``
                                   (counter RNG, shared with ``repro.kernels``),
  * ``materialize()``            — explicit S for tests / tiny problems.

A registry (``@register(kind)`` → ``make_operator``) replaces every if-chain dispatch,
including the ``use_kernel`` routing into the Pallas kernels. Multi-worker callers use

  * :func:`apply_batched` — vmap ``q`` independent sketches over a *single* read of A
    (Algorithm 1's master-sketch mode, IHS's per-iteration sketches, head fitting),
  * :func:`sketch_data_batched` — the batched ``(S_k A, S_k b)`` pairs of Algorithm 1.

Randomness contract
-------------------
All per-element randomness is counter-based (threefry2x32 from ``repro.kernels.common``):
entry/row parameters are pure functions of ``(key, global index)``. This is what makes
``apply_blocked`` produce bit-comparable results for *any* block size, and what lets
the Pallas Gaussian/SJLT kernels draw the *same* S as the pure-jnp paths. Only the
O(m) row-sampling draws (uniform/leverage/SRHT row picks, hybrid's row subset) use
ordinary ``jax.random`` calls — they are tiny and never need streaming.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import sketches as sk
from repro.kernels import common as kcommon
from repro.utils import env as envcfg

# Default row-tile for blocked/streamed application. 4096 rows × 512 cols of f32 is
# 8 MiB — comfortably inside a v5e core's VMEM budget alongside the (m, block) S tile.
DEFAULT_BLOCK_ROWS = 4096


# ----------------------------------------------------------------------- registry

_REGISTRY: Dict[str, type] = {}


def register(kind: str) -> Callable[[type], type]:
    """Class decorator: make ``kind`` constructible through :func:`make_operator`."""

    def deco(cls: type) -> type:
        _REGISTRY[kind] = cls
        return cls

    return deco


def registered_kinds() -> tuple:
    return tuple(sorted(_REGISTRY))


def make_operator(
    spec: sk.SketchSpec,
    key: jax.Array,
    n: int,
    *,
    scores: Optional[jax.Array] = None,
) -> "SketchOp":
    """Build the frozen ``S ∈ R^{m×n}`` described by ``spec`` from ``key``.

    ``scores``: leverage scores (required for ``kind="leverage"``, ignored otherwise);
    data-dependent sketches must be given their data statistics explicitly so the
    resulting object is a *fixed* linear operator.
    """
    try:
        cls = _REGISTRY[spec.kind]
    except KeyError:
        raise ValueError(
            f"no SketchOp registered for kind {spec.kind!r}; known: {registered_kinds()}"
        ) from None
    return cls.build(spec, key, n, scores=scores)


# --------------------------------------------------------------------- shape utils


def _to_2d(X: jax.Array, rows: int):
    """View (rows, ...) as (rows, k); returns the 2-D view and the trailing shape."""
    if X.shape[0] != rows:
        raise ValueError(f"operator expects leading dim {rows}, got shape {X.shape}")
    return X.reshape(rows, -1), X.shape[1:]


def _from_2d(Y2: jax.Array, batch: tuple) -> jax.Array:
    return Y2.reshape((Y2.shape[0],) + batch)


def _scan_row_blocks(
    A2: jax.Array, n: int, block_rows: int, init: jax.Array, reducer, *, double_buffer: bool = True
):
    """Shared blocked-streaming scaffold: ``lax.scan`` of ``reducer(acc, j0, A_blk)``
    over zero-padded f32 row tiles of A2 (2-D). Zero rows beyond n contribute
    nothing to any registered reducer (matmul against zeros / gather of zeros /
    scatter of zeros), so no masking is needed.

    Double-buffered by default: the scan carry holds the *prefetched* next tile
    alongside the accumulator, and each step issues the fetch of tile i+1 before
    consuming tile i. The fetch has no data dependence on the reduction, so XLA is
    free to overlap the copy/DMA of the next tile with the current tile's matmul —
    the classic two-slot pipeline, expressed as an async-friendly scan carry. The
    eager pre-reshaped path is kept (``double_buffer=False``) as the reference.
    """
    bs = max(1, min(block_rows, n))
    nb = -(-n // bs)
    if nb * bs != n:
        A2 = jnp.pad(A2, ((0, nb * bs - n), (0, 0)))
    Af = A2.astype(jnp.float32)

    if nb == 1:
        return reducer(init, jnp.int32(0), Af)

    if not double_buffer:
        blocks = Af.reshape(nb, bs, Af.shape[1])
        j0s = jnp.arange(nb, dtype=jnp.int32) * bs

        def body(acc, xs):
            j0, Ab = xs
            return reducer(acc, j0, Ab), None

        acc, _ = jax.lax.scan(body, init, (j0s, blocks))
        return acc

    def fetch(i):
        return jax.lax.dynamic_slice_in_dim(Af, i * bs, bs, axis=0)

    def body(carry, i):
        acc, cur = carry
        nxt = fetch(jnp.minimum(i + 1, nb - 1))  # prefetch: independent of the reduce
        acc = reducer(acc, i * bs, cur)
        return (acc, nxt), None

    (acc, _), _ = jax.lax.scan(body, (init, fetch(jnp.int32(0))), jnp.arange(nb, dtype=jnp.int32))
    return acc


def _scan_row_blocks_joint(
    A2: jax.Array, B2: jax.Array, n: int, block_rows: int, init: jax.Array, reducer
):
    """Like :func:`_scan_row_blocks`, but streams matching row tiles of two arrays
    and hands the reducer their *tile-level* join ``[A_blk | B_blk]``.

    Joining per tile keeps the copy cache-resident (the joined tile is consumed
    immediately), instead of materializing a full (n, d+k) concatenation in HBM
    and re-reading it — one whole DRAM round trip of A saved per gram pass.
    """
    bs = max(1, min(block_rows, n))
    nb = -(-n // bs)
    if nb * bs != n:
        A2 = jnp.pad(A2, ((0, nb * bs - n), (0, 0)))
        B2 = jnp.pad(B2, ((0, nb * bs - n), (0, 0)))
    Af = A2.astype(jnp.float32)
    Bf = B2.astype(jnp.float32)

    def fetch(i):
        return jnp.concatenate(
            [
                jax.lax.dynamic_slice_in_dim(Af, i * bs, bs, axis=0),
                jax.lax.dynamic_slice_in_dim(Bf, i * bs, bs, axis=0),
            ],
            axis=1,
        )

    if nb == 1:
        return reducer(init, jnp.int32(0), fetch(jnp.int32(0)))

    def body(carry, i):
        acc, cur = carry
        nxt = fetch(jnp.minimum(i + 1, nb - 1))  # prefetch: independent of the reduce
        acc = reducer(acc, i * bs, cur)
        return (acc, nxt), None

    (acc, _), _ = jax.lax.scan(body, (init, fetch(jnp.int32(0))), jnp.arange(nb, dtype=jnp.int32))
    return acc


def _join_b(A: jax.Array, b: Optional[jax.Array]):
    """Stack ``[A | b]`` so one pass sketches both; returns the joined 2-D matrix."""
    if A.ndim != 2:
        raise ValueError(f"gram_blocked expects A of shape (n, d), got {A.shape}")
    if b is None:
        return A
    bm = b if b.ndim == 2 else b[:, None]
    with jax.named_scope(kcommon.GRAM_INPUT_SCOPE):
        return jnp.concatenate([A, bm.astype(A.dtype)], axis=1)


def _split_gram(Gf: jax.Array, d: int, b: Optional[jax.Array]):
    """Carve (G, c) out of the joint Gram of [A | b]: G = (SA)ᵀ(SA), c = (SA)ᵀ(Sb)."""
    G = Gf[:d, :d]
    if b is None:
        return G, None
    c = Gf[:d, d:]
    return G, (c[:, 0] if b.ndim == 1 else c)


def _split_gram_batched(Gf: jax.Array, d: int, b: Optional[jax.Array]):
    """Batched :func:`_split_gram`: carve (q, d, d) G's and (q, d[, k]) c's out of
    the (q, d+k, d+k) joint Grams of [A | b]."""
    G = Gf[:, :d, :d]
    if b is None:
        return G, None
    c = Gf[:, :d, d:]
    return G, (c[..., 0] if b.ndim == 1 else c)


def _gather_rows_reducer(rows: jax.Array):
    """Reducer accumulating ``A[rows]`` from row blocks: O(len(rows)·k) per block
    (a mask-and-gather), not a dense one-hot matmul."""

    def reducer(acc, j0, Ab):
        local = rows - j0
        in_blk = (local >= 0) & (local < Ab.shape[0])
        idx = jnp.clip(local, 0, Ab.shape[0] - 1)
        return acc + jnp.where(in_blk[:, None], jnp.take(Ab, idx, axis=0), 0.0)

    return reducer


# -------------------------------------------------------------------------- base


@dataclasses.dataclass(frozen=True)
class SketchOp:
    """Frozen linear operator S ∈ R^{m×n} (base class).

    Subclasses either implement :meth:`columns` — an arbitrary column block of S,
    valid for traced start offsets — and inherit generic blocked apply/adjoint, or
    override the generic methods with cheaper structure-aware code (SJLT, hybrid).
    """

    spec: sk.SketchSpec
    key: jax.Array
    n: int

    @property
    def m(self) -> int:
        return self.spec.m

    @property
    def shape(self) -> tuple:
        return (self.m, self.n)

    # -- construction ------------------------------------------------------------

    @classmethod
    def build(cls, spec, key, n, *, scores=None) -> "SketchOp":
        raise NotImplementedError

    # Kinds with a multi-worker fused gram kernel override this with a classmethod
    # ``(spec, keys, A, b) -> (Gs, cs)``: all ``q`` workers' joint Grams from one
    # kernel pass over A, worker slice ``w`` bitwise-identical to the per-key
    # kernel path under ``keys[w]``. Kinds without one (None) always take the
    # per-key route in :func:`gram_batched`.
    gram_batched_kernel = None

    # -- required tile primitive --------------------------------------------------

    def columns(self, j0, block: int) -> jax.Array:
        """``S[:, j0 : j0+block]`` as an (m, block) tile. ``j0`` may be traced.

        Column indices ≥ n are permitted (blocked application pads A's rows with
        zeros, so out-of-range columns multiply zeros and contribute nothing); the
        values there only need to be finite.
        """
        raise NotImplementedError(f"{type(self).__name__} does not expose S tiles")

    # -- operator calculus --------------------------------------------------------

    def apply(self, A: jax.Array) -> jax.Array:
        """``S @ A`` for A of shape (n, ...). Default: one full-width tile."""
        A2, batch = _to_2d(A, self.n)
        out = (self.columns(0, self.n) @ A2.astype(jnp.float32)).astype(A.dtype)
        return _from_2d(out, batch)

    def _stream_pieces(self, k: int):
        """The kind's blocked-streaming triple ``(init, reducer, finish)`` for a
        width-k right-hand side: ``acc := init``; ``acc = reducer(acc, j0, tile)``
        over row tiles; ``S @ X = finish(acc)``. One primitive powers both
        :meth:`apply_blocked` and the fused :meth:`gram_blocked`.

        Default: dense S tiles from :meth:`columns` (Gaussian, SRHT closed form).
        """
        init = jnp.zeros((self.m, k), jnp.float32)
        reducer = lambda acc, j0, Ab: acc + self.columns(j0, Ab.shape[0]) @ Ab
        return init, reducer, lambda acc: acc

    def apply_blocked(
        self, A: jax.Array, *, block_rows: int = DEFAULT_BLOCK_ROWS
    ) -> jax.Array:
        """``S @ A`` streamed as a ``lax.scan`` over row tiles of A.

        Peak live memory is O(block_rows · k + m · k) instead of O(n · k): the
        sketch never needs all of A resident. Matches :meth:`apply` to float
        tolerance for any ``block_rows`` (including ones that don't divide n).
        """
        A2, batch = _to_2d(A, self.n)
        init, reducer, finish = self._stream_pieces(A2.shape[1])
        acc = _scan_row_blocks(A2, self.n, block_rows, init, reducer)
        return _from_2d(finish(acc).astype(A.dtype), batch)

    def adjoint(self, Y: jax.Array, *, block_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
        """``Sᵀ @ Y`` for Y of shape (m, ...), streamed over column tiles of S."""
        Y2, batch = _to_2d(Y, self.m)
        Yf = Y2.astype(jnp.float32)
        bs = max(1, min(block_rows, self.n))
        nb = -(-self.n // bs)
        j0s = jnp.arange(nb, dtype=jnp.int32) * bs

        def body(_, j0):
            return None, self.columns(j0, bs).T @ Yf  # (bs, k)

        _, outs = jax.lax.scan(body, None, j0s)
        out = outs.reshape(nb * bs, Yf.shape[1])[: self.n]
        return _from_2d(out.astype(Y.dtype), batch)

    def gram_blocked(
        self,
        A: jax.Array,
        b: Optional[jax.Array] = None,
        *,
        block_rows: int = DEFAULT_BLOCK_ROWS,
    ):
        """Fused single-pass sketch→Gram: ``(G, c)`` with ``G = (SA)ᵀ(SA)`` (d, d)
        and ``c = (SA)ᵀ(Sb)`` (``None`` when b is), from ONE streamed pass over
        ``[A | b]``.

        This is everything the sketched normal equations need — the m×d problem is
        then a Cholesky on G. The (m, d+k) sketch accumulator rides in the scan
        carry (double-buffered row tiles, with ``[A_blk | b_blk]`` joined at tile
        granularity so no full concatenation ever hits HBM); SA is never written
        back for large n, and the Gram is a single tiny trailing contraction.
        Kernel-routed kinds override this with fully fused Pallas kernels that
        also keep S in-core.
        """
        if A.ndim != 2:
            raise ValueError(f"gram_blocked expects A of shape (n, d), got {A.shape}")
        bm = None if b is None else (b if b.ndim == 2 else b[:, None])
        k = A.shape[1] + (0 if bm is None else bm.shape[1])
        init, reducer, finish = self._stream_pieces(k)
        if bm is None:
            acc = _scan_row_blocks(A, self.n, block_rows, init, reducer)
        else:
            acc = _scan_row_blocks_joint(A, bm, self.n, block_rows, init, reducer)
        SAb = finish(acc).astype(jnp.float32)
        return _split_gram(SAb.T @ SAb, A.shape[1], b)

    def materialize(self, dtype=jnp.float32) -> jax.Array:
        """Explicit S ∈ R^{m×n} (tests / small problems only)."""
        return self.apply(jnp.eye(self.n, dtype=dtype))


# ----------------------------------------------------------------------- gaussian


@register("gaussian")
@dataclasses.dataclass(frozen=True)
class GaussianOp(SketchOp):
    """i.i.d. N(0, 1/m) entries from the counter stream: S[i, j] = f(key, i, j).

    The exact same stream the RNG-fused Pallas kernel generates tile-by-tile
    (``repro.kernels.gaussian``), so the kernel path, the jnp path, blocked
    streaming, and the adjoint all agree on S.
    """

    k0: jax.Array = None
    k1: jax.Array = None

    @classmethod
    def build(cls, spec, key, n, *, scores=None):
        k0, k1 = kcommon.key_to_words(key)
        return cls(spec=spec, key=key, n=n, k0=k0, k1=k1)

    def columns(self, j0, block: int) -> jax.Array:
        rows = jax.lax.broadcasted_iota(jnp.uint32, (self.m, block), 0)
        cols = jnp.uint32(j0) + jax.lax.broadcasted_iota(jnp.uint32, (self.m, block), 1)
        z = kcommon.counter_normal(self.k0, self.k1, rows, cols)
        return z * jnp.float32(1.0 / math.sqrt(self.m))

    def apply(self, A: jax.Array) -> jax.Array:
        if self.spec.use_kernel:
            from repro.kernels.gaussian import ops as gops

            A2, batch = _to_2d(A, self.n)
            return _from_2d(gops.gaussian_sketch(self.key, A2, self.m), batch)
        return super().apply(A)

    def adjoint(self, Y: jax.Array, *, block_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
        if self.spec.use_kernel:
            from repro.kernels.gaussian import ops as gops

            Y2, batch = _to_2d(Y, self.m)
            out = gops.gaussian_adjoint(self.key, Y2, self.n)
            return _from_2d(out.astype(Y.dtype), batch)
        return super().adjoint(Y, block_rows=block_rows)

    def gram_blocked(
        self,
        A: jax.Array,
        b: Optional[jax.Array] = None,
        *,
        block_rows: int = DEFAULT_BLOCK_ROWS,
    ):
        if self.spec.use_kernel:
            from repro.kernels.gaussian import ops as gops

            Gf = gops.gaussian_gram(self.key, _join_b(A, b), self.m)
            return _split_gram(Gf, A.shape[1], b)
        return super().gram_blocked(A, b, block_rows=block_rows)

    @classmethod
    def gram_batched_kernel(cls, spec, keys, A, b):
        from repro.kernels.gaussian import ops as gops

        Gf = gops.gaussian_gram_multi(keys, _join_b(A, b), spec.m)
        return _split_gram_batched(Gf, A.shape[1], b)


# --------------------------------------------------------------------- rademacher


@register("rademacher")
@dataclasses.dataclass(frozen=True)
class RademacherOp(SketchOp):
    """i.i.d. ±1/√m entries from the *packed* counter stream: sign(i, j) is bit
    ``j % 32`` of ``threefry(key, i, j // 32)`` — one threefry call per 32 entries
    (``kernels.common.packed_sign_words``), versus one call plus Box-Muller per
    entry for the Gaussian family. Sub-gaussian, so Thm-1-style averaging and the
    embedding bounds carry over (arXiv:2412.20301, arXiv:2203.09755); use it when
    the Gaussian path is RNG-bound. Kernel and jnp paths share the same S.
    """

    k0: jax.Array = None
    k1: jax.Array = None

    @classmethod
    def build(cls, spec, key, n, *, scores=None):
        k0, k1 = kcommon.key_to_words(key)
        return cls(spec=spec, key=key, n=n, k0=k0, k1=k1)

    def columns(self, j0, block: int) -> jax.Array:
        signs = kcommon.counter_rademacher_block(self.k0, self.k1, 0, j0, self.m, block)
        return signs * jnp.float32(1.0 / math.sqrt(self.m))

    def apply(self, A: jax.Array) -> jax.Array:
        if self.spec.use_kernel:
            from repro.kernels.rademacher import ops as rops

            A2, batch = _to_2d(A, self.n)
            return _from_2d(rops.rademacher_sketch(self.key, A2, self.m), batch)
        return super().apply(A)

    def gram_blocked(
        self,
        A: jax.Array,
        b: Optional[jax.Array] = None,
        *,
        block_rows: int = DEFAULT_BLOCK_ROWS,
    ):
        if self.spec.use_kernel:
            from repro.kernels.rademacher import ops as rops

            Gf = rops.rademacher_gram(self.key, _join_b(A, b), self.m)
            return _split_gram(Gf, A.shape[1], b)
        return super().gram_blocked(A, b, block_rows=block_rows)

    @classmethod
    def gram_batched_kernel(cls, spec, keys, A, b):
        from repro.kernels.rademacher import ops as rops

        Gf = rops.rademacher_gram_multi(keys, _join_b(A, b), spec.m)
        return _split_gram_batched(Gf, A.shape[1], b)


# -------------------------------------------------------------------------- srht


@register("srht")
@dataclasses.dataclass(frozen=True)
class SRHTOp(SketchOp):
    """Randomized Hadamard (ROS): S = (1/√m) · P · H · D on the 2^⌈log n⌉ padding.

    ``apply`` uses the O(n log n) FWHT (Pallas kernel when requested); ``columns``
    builds Hadamard tiles H[r, j] = (−1)^popcount(r & j) on the fly, which is what
    makes blocked/streamed application possible without the full transform.
    """

    kd0: jax.Array = None  # sign-counter key words (D diagonal)
    kd1: jax.Array = None
    rows: jax.Array = None  # (m,) sampled Hadamard rows, with replacement
    n_pad: int = 0

    @classmethod
    def build(cls, spec, key, n, *, scores=None):
        n_pad = sk.next_pow2(n)
        with jax.named_scope(kcommon.SKETCH_PARAMS_SCOPE):
            kd, kp = jax.random.split(key)
            kd0, kd1 = kcommon.key_to_words(kd)
            rows = jax.random.randint(kp, (spec.m,), 0, n_pad)
        return cls(spec=spec, key=key, n=n, kd0=kd0, kd1=kd1, rows=rows, n_pad=n_pad)

    def _signs(self, j: jax.Array) -> jax.Array:
        """Rademacher diagonal D at (possibly traced) coordinate(s) j."""
        with jax.named_scope(kcommon.SKETCH_PARAMS_SCOPE):
            return kcommon.counter_rademacher(
                self.kd0, self.kd1, j.astype(jnp.uint32), jnp.uint32(0)
            )

    def apply(self, A: jax.Array) -> jax.Array:
        A2, batch = _to_2d(A, self.n)
        DA = A2.astype(jnp.float32) * self._signs(jnp.arange(self.n))[:, None]
        if self.n_pad != self.n:
            DA = jnp.pad(DA, ((0, self.n_pad - self.n), (0, 0)))
        if self.spec.use_kernel:
            from repro.kernels.fwht import ops as fops

            HDA = fops.fwht(DA)
        else:
            HDA = sk._fwht(DA)
        out = jnp.take(HDA, self.rows, axis=0) * jnp.float32(1.0 / math.sqrt(self.m))
        return _from_2d(out.astype(A.dtype), batch)

    def columns(self, j0, block: int) -> jax.Array:
        j = jnp.uint32(j0) + jnp.arange(block, dtype=jnp.uint32)
        # Sylvester closed form: H[r, j] = (−1)^popcount(r & j) — no transform needed.
        parity = jax.lax.population_count(self.rows.astype(jnp.uint32)[:, None] & j[None, :])
        h = (1 - 2 * (parity & jnp.uint32(1)).astype(jnp.int32)).astype(jnp.float32)
        return h * self._signs(j)[None, :] * jnp.float32(1.0 / math.sqrt(self.m))

    def adjoint(self, Y: jax.Array, *, block_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
        Y2, batch = _to_2d(Y, self.m)
        # Sᵀ = (1/√m) · D · Hᵀ · Pᵀ with H symmetric; Pᵀ is scatter-add (P repeats rows).
        Z = jnp.zeros((self.n_pad, Y2.shape[1]), jnp.float32).at[self.rows].add(
            Y2.astype(jnp.float32)
        )
        HZ = sk._fwht(Z)[: self.n]
        out = HZ * self._signs(jnp.arange(self.n))[:, None] * jnp.float32(1.0 / math.sqrt(self.m))
        return _from_2d(out.astype(Y.dtype), batch)

    def gram_blocked(
        self,
        A: jax.Array,
        b: Optional[jax.Array] = None,
        *,
        block_rows: int = DEFAULT_BLOCK_ROWS,
    ):
        if self.spec.use_kernel:
            from repro.kernels.fwht import ops as fops

            key_words = jnp.stack([self.kd0, self.kd1])
            Gf = fops.srht_gram(_join_b(A, b), self.rows, key_words)
            return _split_gram(Gf, A.shape[1], b)
        # Non-kernel: the transform is global, so streamed Sylvester tiles would
        # trade the O(n log n · k) FWHT for an O(n·m·k) matmul — a big loss. One
        # FWHT apply then the tiny (m, d+k) Gram is the fast single pass here;
        # only the Pallas closed-form kernel makes true tile streaming pay.
        SAb = self.apply(_join_b(A, b)).astype(jnp.float32)
        return _split_gram(SAb.T @ SAb, A.shape[1], b)

    @classmethod
    def gram_batched_kernel(cls, spec, keys, A, b):
        from repro.kernels.fwht import ops as fops

        n_pad = sk.next_pow2(A.shape[0])

        def params(key):
            # Mirrors build() exactly — vmapped jax.random draws are elementwise
            # deterministic per key, so rows/words bitwise-match the per-op build.
            kd, kp = jax.random.split(key)
            kd0, kd1 = kcommon.key_to_words(kd)
            rows = jax.random.randint(kp, (spec.m,), 0, n_pad)
            return rows, jnp.stack([kd0, kd1])

        with jax.named_scope(kcommon.SKETCH_PARAMS_SCOPE):
            rows, key_words = jax.vmap(params)(keys)
        Gf = fops.srht_gram_multi(_join_b(A, b), rows, key_words)
        return _split_gram_batched(Gf, A.shape[1], b)


# ------------------------------------------------------------------ row sampling


@register("uniform")
@dataclasses.dataclass(frozen=True)
class UniformOp(SketchOp):
    """Uniform row sampling scaled by √(n/m) so E[SᵀS] = I."""

    rows: jax.Array = None  # (m,)

    @classmethod
    def build(cls, spec, key, n, *, scores=None):
        if spec.replacement:
            rows = jax.random.randint(key, (spec.m,), 0, n)
        else:
            # Gumbel top-k == sampling without replacement, jit-friendly.
            g = jax.random.gumbel(key, (n,))
            rows = jax.lax.top_k(g, spec.m)[1]
        return cls(spec=spec, key=key, n=n, rows=rows)

    @property
    def _scale(self) -> float:
        return math.sqrt(self.n / self.m)

    def apply(self, A: jax.Array) -> jax.Array:
        return jnp.take(A, self.rows, axis=0) * jnp.asarray(self._scale, A.dtype)

    def columns(self, j0, block: int) -> jax.Array:
        j = jnp.int32(j0) + jnp.arange(block, dtype=jnp.int32)
        onehot = (self.rows[:, None] == j[None, :]).astype(jnp.float32)
        return onehot * jnp.float32(self._scale)

    def _stream_pieces(self, k: int):
        init = jnp.zeros((self.m, k), jnp.float32)
        return init, _gather_rows_reducer(self.rows), lambda acc: acc * jnp.float32(self._scale)

    def adjoint(self, Y: jax.Array, *, block_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
        Y2, batch = _to_2d(Y, self.m)
        out = jnp.zeros((self.n, Y2.shape[1]), Y2.dtype).at[self.rows].add(Y2)
        return _from_2d(out * jnp.asarray(self._scale, Y.dtype), batch)


@register("leverage")
@dataclasses.dataclass(frozen=True)
class LeverageOp(SketchOp):
    """Leverage-score sampling: P[row j] ∝ ℓ_j, kept row scaled by 1/√(m·p_j)."""

    rows: jax.Array = None  # (m,)
    scales: jax.Array = None  # (m,)

    @classmethod
    def build(cls, spec, key, n, *, scores=None):
        if scores is None:
            raise ValueError(
                "leverage sketches are data-dependent: pass scores= to make_operator "
                "(e.g. sketches.leverage_scores(A)) so the operator is fixed"
            )
        p = scores / jnp.sum(scores)
        rows = jax.random.categorical(key, jnp.log(p + 1e-30), shape=(spec.m,))
        scales = 1.0 / jnp.sqrt(spec.m * jnp.take(p, rows))
        return cls(spec=spec, key=key, n=n, rows=rows, scales=scales)

    def apply(self, A: jax.Array) -> jax.Array:
        scl = self.scales.astype(A.dtype)
        return jnp.take(A, self.rows, axis=0) * scl.reshape((self.m,) + (1,) * (A.ndim - 1))

    def columns(self, j0, block: int) -> jax.Array:
        j = jnp.int32(j0) + jnp.arange(block, dtype=jnp.int32)
        onehot = (self.rows[:, None] == j[None, :]).astype(jnp.float32)
        return onehot * self.scales.astype(jnp.float32)[:, None]

    def _stream_pieces(self, k: int):
        init = jnp.zeros((self.m, k), jnp.float32)
        finish = lambda acc: acc * self.scales.astype(jnp.float32)[:, None]
        return init, _gather_rows_reducer(self.rows), finish

    def adjoint(self, Y: jax.Array, *, block_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
        Y2, batch = _to_2d(Y, self.m)
        contrib = Y2 * self.scales.astype(Y2.dtype)[:, None]
        out = jnp.zeros((self.n, Y2.shape[1]), Y2.dtype).at[self.rows].add(contrib)
        return _from_2d(out, batch)


# -------------------------------------------------------------------------- sjlt


@register("sjlt")
@dataclasses.dataclass(frozen=True)
class SJLTOp(SketchOp):
    """Sparse JL: s nonzeros (±1/√s) per input coordinate, counter-derived per row.

    Row parameters come from :func:`repro.kernels.common.sjlt_counter_params`, the
    same draw the Pallas kernel consumes — kernel and jnp paths share S exactly.
    """

    k0: jax.Array = None
    k1: jax.Array = None

    @classmethod
    def build(cls, spec, key, n, *, scores=None):
        k0, k1 = kcommon.key_to_words(key)
        return cls(spec=spec, key=key, n=n, k0=k0, k1=k1)

    def _params(self, row_idx: jax.Array):
        with jax.named_scope(kcommon.SKETCH_PARAMS_SCOPE):
            return kcommon.sjlt_counter_params(self.k0, self.k1, row_idx, self.spec.s, self.m)

    def _segment_apply(self, A2: jax.Array, row_idx: jax.Array) -> jax.Array:
        buckets, signs = self._params(row_idx)
        r, s = buckets.shape
        vals = (signs[..., None] * A2[:, None, :]).reshape(r * s, A2.shape[1])
        return jax.ops.segment_sum(vals, buckets.reshape(-1), num_segments=self.m)

    def apply(self, A: jax.Array) -> jax.Array:
        A2, batch = _to_2d(A, self.n)
        if self.spec.use_kernel:
            from repro.kernels.sjlt import ops as sops

            buckets, signs = self._params(jnp.arange(self.n))
            out = sops.sjlt_apply(A2, buckets, signs, self.m)
        else:
            out = self._segment_apply(A2.astype(jnp.float32), jnp.arange(self.n)).astype(A.dtype)
        return _from_2d(out, batch)

    def _stream_pieces(self, k: int):
        init = jnp.zeros((self.m, k), jnp.float32)
        reducer = lambda acc, j0, Ab: acc + self._segment_apply(
            Ab, j0 + jnp.arange(Ab.shape[0], dtype=jnp.int32)
        )
        return init, reducer, lambda acc: acc

    def adjoint(self, Y: jax.Array, *, block_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
        Y2, batch = _to_2d(Y, self.m)
        buckets, signs = self._params(jnp.arange(self.n))  # (n, s)
        gathered = jnp.take(Y2.astype(jnp.float32), buckets, axis=0)  # (n, s, k)
        out = jnp.sum(gathered * signs[..., None], axis=1)
        return _from_2d(out.astype(Y.dtype), batch)

    def gram_blocked(
        self,
        A: jax.Array,
        b: Optional[jax.Array] = None,
        *,
        block_rows: int = DEFAULT_BLOCK_ROWS,
    ):
        if self.spec.use_kernel:
            from repro.kernels.sjlt import ops as sops

            buckets, signs = self._params(jnp.arange(self.n))
            Gf = sops.sjlt_gram(_join_b(A, b), buckets, signs, self.m)
            return _split_gram(Gf, A.shape[1], b)
        return super().gram_blocked(A, b, block_rows=block_rows)

    @classmethod
    def gram_batched_kernel(cls, spec, keys, A, b):
        from repro.kernels.sjlt import ops as sops

        with jax.named_scope(kcommon.SKETCH_PARAMS_SCOPE):
            row_idx = jnp.arange(A.shape[0])
            words = kcommon.keys_to_words(keys)  # (q, 2) — same words build() derives
            buckets, signs = jax.vmap(
                lambda w: kcommon.sjlt_counter_params(w[0], w[1], row_idx, spec.s, spec.m)
            )(words)
        Gf = sops.sjlt_gram_multi(_join_b(A, b), buckets, signs, spec.m)
        return _split_gram_batched(Gf, A.shape[1], b)


# ------------------------------------------------------------------------ hybrid


@register("hybrid")
@dataclasses.dataclass(frozen=True)
class HybridOp(SketchOp):
    """Paper §IV-D: uniform-sample m′ rows without replacement (what a worker can
    afford to *read*), then an inner sketch m′ → m (what it can afford to *compute*).

    S = S_inner · U with U the scaled row-subset selector; the operator calculus
    composes: apply = inner∘gather, adjoint = scatter∘innerᵀ."""

    rows: jax.Array = None  # (m_prime,)
    inner: SketchOp = None

    @classmethod
    def build(cls, spec, key, n, *, scores=None):
        k1, k2 = jax.random.split(key)
        g = jax.random.gumbel(k1, (n,))
        rows = jax.lax.top_k(g, spec.m_prime)[1]
        inner_spec = sk.SketchSpec(spec.inner, spec.m, s=spec.s, use_kernel=spec.use_kernel)
        inner = make_operator(inner_spec, k2, spec.m_prime)
        return cls(spec=spec, key=key, n=n, rows=rows, inner=inner)

    @property
    def _scale(self) -> float:
        return math.sqrt(self.n / self.spec.m_prime)

    def apply(self, A: jax.Array) -> jax.Array:
        sampled = jnp.take(A, self.rows, axis=0) * jnp.asarray(self._scale, A.dtype)
        return self.inner.apply(sampled)

    def _stream_pieces(self, k: int):
        # The m′×k intermediate is exactly the "what a worker reads" budget — it is
        # the one thing hybrid sketching keeps resident while streaming over n.
        init = jnp.zeros((self.spec.m_prime, k), jnp.float32)
        finish = lambda acc: self.inner.apply(acc * jnp.float32(self._scale))
        return init, _gather_rows_reducer(self.rows), finish

    def adjoint(self, Y: jax.Array, *, block_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
        Y2, batch = _to_2d(Y, self.m)
        z = self.inner.adjoint(Y2)  # (m_prime, k)
        out = jnp.zeros((self.n, z.shape[1]), z.dtype).at[self.rows].add(z)
        return _from_2d(out * jnp.asarray(self._scale, Y.dtype), batch)


# --------------------------------------------------------- functional entry points


def _scores_for(spec: sk.SketchSpec, A: jax.Array, scores) -> Optional[jax.Array]:
    if spec.kind == "leverage" and scores is None:
        return sk.leverage_scores(A.reshape(A.shape[0], -1))
    return scores


def apply(spec: sk.SketchSpec, key: jax.Array, A: jax.Array, *, scores=None) -> jax.Array:
    """``S @ A`` — the registry-dispatched replacement for the old if-chain."""
    scores = _scores_for(spec, A, scores)
    return make_operator(spec, key, A.shape[0], scores=scores).apply(A)


def apply_blocked(
    spec: sk.SketchSpec,
    key: jax.Array,
    A: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    scores=None,
) -> jax.Array:
    """``S @ A`` streamed over row tiles (out-of-core n)."""
    scores = _scores_for(spec, A, scores)
    return make_operator(spec, key, A.shape[0], scores=scores).apply_blocked(
        A, block_rows=block_rows
    )


def gram_blocked(
    spec: sk.SketchSpec,
    key: jax.Array,
    A: jax.Array,
    b: Optional[jax.Array] = None,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    scores=None,
):
    """Fused single-pass ``(G, c) = ((SA)ᵀ(SA), (SA)ᵀ(Sb))`` — registry-dispatched."""
    scores = _scores_for(spec, A, scores)
    return make_operator(spec, key, A.shape[0], scores=scores).gram_blocked(
        A, b, block_rows=block_rows
    )


def gram_blocked_host(
    spec: sk.SketchSpec,
    key: jax.Array,
    A,
    b=None,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    scores=None,
):
    """Out-of-core :func:`gram_blocked` for A living on the HOST (numpy array or
    ``np.memmap``): n can exceed *device* memory, not just VMEM.

    Streams row tiles through the same ``(init, reducer, finish)`` triple as the
    on-device path, but the scan loop runs in Python with double-buffered async
    ``jax.device_put``: the H2D transfer of tile i+1 is issued *before* the jitted
    reduce step of tile i is dispatched, so (dispatch being async) the copy
    overlaps the compute — the two-slot pipeline of ``_scan_row_blocks``, with the
    host→device link in place of the HBM fetch. Tiles are joined ``[A_blk|b_blk]``
    and zero-padded to a constant shape host-side (one jit compile; zero rows
    contribute nothing to any registered reducer). Device-resident peak memory is
    O(block_rows·k + m·k). The counter-RNG contract makes the result match
    ``gram_blocked`` on device-resident A to float tolerance for any block size.
    """
    import numpy as np

    if A.ndim != 2:
        raise ValueError(f"gram_blocked_host expects A of shape (n, d), got {A.shape}")
    n, d = A.shape
    bm = None if b is None else (b if b.ndim == 2 else np.asarray(b)[:, None])
    k = d + (0 if bm is None else bm.shape[1])
    op = make_operator(spec, key, n, scores=scores)
    init, reducer, finish = op._stream_pieces(k)

    bs = max(1, min(block_rows, n))
    nb = -(-n // bs)

    @jax.jit
    def step(acc, j0, tile):
        return reducer(acc, j0, tile)

    def host_tile(i: int) -> np.ndarray:
        j0 = i * bs
        blk = np.asarray(A[j0 : j0 + bs], dtype=np.float32)
        if bm is not None:
            blk = np.concatenate([blk, np.asarray(bm[j0 : j0 + bs], dtype=np.float32)], axis=1)
        if blk.shape[0] < bs:
            blk = np.concatenate([blk, np.zeros((bs - blk.shape[0], k), np.float32)], axis=0)
        return blk

    acc = init
    nxt = jax.device_put(host_tile(0))
    for i in range(nb):
        cur = nxt
        if i + 1 < nb:
            nxt = jax.device_put(host_tile(i + 1))  # in flight while step(i) runs
        acc = step(acc, jnp.int32(i * bs), cur)
    SAb = finish(acc).astype(jnp.float32)
    return _split_gram(SAb.T @ SAb, d, b)


# ------------------------------------------------------- multi-worker batching


def mesh_world(mesh, axis_names) -> int:
    q = 1
    for name in axis_names:
        q *= mesh.shape[name]
    return q


def _mesh_batch_enabled() -> bool:
    """Whether batched dispatch may shard worker keys over a provided mesh.

    On real accelerator meshes each worker's sketch runs on its own chip — a q×
    compute win. Forced host "devices" (``--xla_force_host_platform_device_count``)
    share one CPU, so sharding there only adds SPMD partitioning overhead on top of
    the same serial FLOPs; the loop fallback is strictly faster. Override with
    ``REPRO_MESH_BATCH=1`` / ``0`` (tests force the mesh path on fake devices to
    check it is bitwise-identical to the loop).
    """
    forced = envcfg.read_bool("REPRO_MESH_BATCH")
    if forced is not None:
        return forced
    return jax.default_backend() != "cpu"


def _mesh_shards_keys(mesh, axis_names, q: int) -> bool:
    """Whether batched dispatch shards the q worker keys over ``mesh``: only a mesh
    of more than one worker shard, dividing q, on a backend where sharding pays
    (:func:`_mesh_batch_enabled`). A one-device mesh shards nothing."""
    if mesh is None or not _mesh_batch_enabled():
        return False
    world = mesh_world(mesh, axis_names)
    return world > 1 and q % world == 0


def _batched_prefers_loop(spec: sk.SketchSpec) -> bool:
    """Backend-aware choice between vmap and a sequential map for worker batching.

    Pallas calls batch unreliably in interpret mode, and the FWHT butterfly vmaps
    poorly off-accelerator — ``results/bench/BENCH_sketch_ops.json`` shows the
    batched SRHT losing to a plain loop on CPU — so both take the sequential map
    (which still reuses the single resident copy of A). Everything else vmaps the
    q projections onto one batched matmul.
    """
    if spec.use_kernel:
        return True
    kinds = {spec.kind} | ({spec.inner} if spec.kind == "hybrid" else set())
    return "srht" in kinds and jax.default_backend() == "cpu"


def _batched_over_keys(per_key, keys: jax.Array, spec: sk.SketchSpec, mesh, axis_names, extras):
    """Run ``per_key(key, *extras)`` for every worker key.

    Dispatch order: ``shard_map`` over the mesh's worker axes when a mesh is given
    and the backend has real devices to shard over (:func:`_mesh_batch_enabled`;
    each shard runs its q/world keys sequentially — bitwise identical to the loop
    fallback under the same keys), else the per-backend loop/vmap choice of
    :func:`_batched_prefers_loop`.
    """
    if _mesh_shards_keys(mesh, axis_names, keys.shape[0]):
        from jax.sharding import PartitionSpec as P

        def worker(keys_blk, *ex):
            return jax.lax.map(lambda k: per_key(k, *ex), keys_blk)

        fn = jax.shard_map(
            worker,
            mesh=mesh,
            in_specs=(P(axis_names),) + tuple(P() for _ in extras),
            out_specs=P(axis_names),
            check_vma=False,  # per_key may run a Pallas kernel (untyped mesh variance)
        )
        return fn(keys, *extras)
    if _batched_prefers_loop(spec):
        return jax.lax.map(lambda k: per_key(k, *extras), keys)
    return jax.vmap(lambda k: per_key(k, *extras))(keys)


def apply_batched(
    spec: sk.SketchSpec,
    keys: jax.Array,
    A: jax.Array,
    *,
    scores=None,
    mesh=None,
    axis_names: tuple = ("workers",),
) -> jax.Array:
    """All ``q`` workers' sketches ``(S_k A)_k`` in one pass over A.

    ``keys``: (q,)-batched PRNG keys (e.g. ``prng.worker_keys``). The q projections
    are either vmapped onto one batched matmul, run as a sequential map (auto-chosen
    per backend — see :func:`_batched_prefers_loop`), or — when ``mesh`` is given
    and q divides the worker-axis world size — sharded across the mesh with one
    replicated read of A per device. Data-dependent statistics (leverage scores)
    are computed once and shared — each worker still draws its own rows.
    Returns a (q, m, ...) stack.
    """
    scores = _scores_for(spec, A, scores)
    n = A.shape[0]
    extras = (A,) + ((scores,) if scores is not None else ())

    def per_key(k, A_, *rest):
        return make_operator(spec, k, n, scores=rest[0] if rest else None).apply(A_)

    return _batched_over_keys(per_key, keys, spec, mesh, axis_names, extras)


def gram_batched(
    spec: sk.SketchSpec,
    keys: jax.Array,
    A: jax.Array,
    b: Optional[jax.Array] = None,
    *,
    scores=None,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    mesh=None,
    axis_names: tuple = ("workers",),
):
    """All ``q`` workers' fused Grams ``(G_k, c_k)`` — the batched form of
    :meth:`SketchOp.gram_blocked`.

    Per worker this moves O(d²) instead of O(m·d) out of the sketch pass (and for
    the fused kernels, nothing of S or SA ever reaches HBM), which is what the
    master-sketch privacy mode ships and what IHS/head-fitting consume. Returns
    ``(Gs, cs)`` of shapes (q, d, d) and (q, d[, k]); ``cs`` is None when b is.

    Kernel-routed kinds with a multi-worker kernel (gaussian/rademacher/sjlt/srht)
    take :meth:`SketchOp.gram_batched_kernel` when no mesh is sharding the keys
    (no mesh, or a one-shard mesh such as one chip): one kernel pass over A for
    all q sketches instead of q kernel launches, bitwise-identical per worker to
    the per-key loop. Which kinds have that kernel is fixed by their class, not
    decided at run time.
    """
    scores = _scores_for(spec, A, scores)
    fused = _REGISTRY[spec.kind].gram_batched_kernel
    sharded = _mesh_shards_keys(mesh, axis_names, keys.shape[0])
    if spec.use_kernel and fused is not None and not sharded:
        return fused(spec, keys, A, b)
    n = A.shape[0]
    extras = (A,) + (() if b is None else (b,)) + ((scores,) if scores is not None else ())

    def per_key(k, A_, *rest):
        rest = list(rest)
        b_ = rest.pop(0) if b is not None else None
        sc = rest.pop(0) if scores is not None else None
        op = make_operator(spec, k, n, scores=sc)
        G, c = op.gram_blocked(A_, b_, block_rows=block_rows)
        return (G, c) if b is not None else G

    out = _batched_over_keys(per_key, keys, spec, mesh, axis_names, extras)
    return out if b is not None else (out, None)


def sketch_data_batched(
    spec: sk.SketchSpec,
    keys: jax.Array,
    A: jax.Array,
    b: jax.Array,
    *,
    mesh=None,
    axis_names: tuple = ("workers",),
) -> tuple:
    """Batched Algorithm-1 master step: ``(S_k A, S_k b)`` for every worker key,
    sketching ``[A | b]`` jointly so each worker's pair shares its S."""
    bm = b if b.ndim == 2 else b[:, None]
    d = A.shape[1]
    SAb = apply_batched(
        spec, keys, jnp.concatenate([A, bm], axis=1), mesh=mesh, axis_names=axis_names
    )
    Sb = SAb[..., d:]
    return SAb[..., :d], (Sb if b.ndim == 2 else Sb[..., 0])
