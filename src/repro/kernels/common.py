"""Shared kernel utilities: in-kernel counter RNG and Hadamard generators.

threefry2x32 is hand-rolled with uint32 jnp ops (shifts/xors/adds) because
``pltpu.prng_*`` has no interpret-mode lowering on CPU; a counter-based RNG is also
exactly what we want architecturally — tile (i, j) of the random sketch is a pure
function of (key, i, j), so grid order, multi-pod sharding, and checkpoint/restart all
reproduce identical sketches with zero coordination.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.utils import env as envcfg

# ``jax.named_scope`` names of the ops made before a fused-Gram kernel, as a device
# trace shows them: the join and zero-pad of [A | b] to the kernel's layout, and the
# sketch's own per-row parameters (SJLT buckets and signs, SRHT rows and signs).
GRAM_INPUT_SCOPE = "repro.gram.input"
SKETCH_PARAMS_SCOPE = "repro.gram.sketch_params"

_ROT = (13, 15, 26, 6, 17, 29, 16, 24)
_PARITY = np.uint32(0x1BD11BDA)
DEFAULT_ROUNDS = 20


# ------------------------------------------------------------- interpret default


def default_interpret() -> bool:
    """Whether Pallas kernels should run in interpret mode on this backend.

    Mosaic lowering only exists for TPU; on CPU (tests, this container) and GPU the
    kernels must run interpreted. Every public kernel op takes ``interpret=None``
    meaning "resolve here", so compiled-vs-interpreted is decided in exactly one
    place instead of hard-coded per call site. ``REPRO_PALLAS_INTERPRET=0/1``
    overrides the autodetection (e.g. to force-interpret on TPU while debugging).
    """
    forced = envcfg.read_bool("REPRO_PALLAS_INTERPRET")
    if forced is not None:
        return forced
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> backend autodetection; anything else is an explicit override."""
    return default_interpret() if interpret is None else bool(interpret)


def _rotl(x: jax.Array, r: int) -> jax.Array:
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def rng_rounds() -> int:
    """Threefry round count for the *Gaussian* counter stream.

    ``REPRO_RNG_ROUNDS`` (default 20, must be a positive multiple of 4) selects a
    reduced-round Threefry variant for the RNG-bound Gaussian family — e.g. 8
    rounds cuts the per-entry uint work 2.5× while staying far above the 13-round
    cryptanalysis margin for *statistical* (non-cryptographic) use. Resolved at
    trace time: set it before the first jit of a Gaussian op (tests/benches use
    subprocesses). Sign-only streams (SJLT params, Rademacher, SRHT diagonals)
    always use the full :data:`DEFAULT_ROUNDS` — their cost is already ≤1 call
    per 32 entries, so there is nothing to win there.
    """
    return envcfg.read_int("REPRO_RNG_ROUNDS", DEFAULT_ROUNDS, positive=True, multiple_of=4)


def threefry2x32(
    k0: jax.Array, k1: jax.Array, c0: jax.Array, c1: jax.Array, *, rounds: int = DEFAULT_ROUNDS
):
    """Threefry-2x32 (20 rounds = the standard variant). All args uint32
    (broadcastable). Returns two uint32 streams with the shapes of (c0, c1)."""
    if rounds <= 0 or rounds % 4:
        raise ValueError(f"threefry rounds must be a positive multiple of 4, got {rounds}")
    ks = (k0, k1, k0 ^ k1 ^ _PARITY)
    x0 = c0 + ks[0]
    x1 = c1 + ks[1]
    for block in range(rounds // 4):
        for r in range(4):
            x0 = x0 + x1
            x1 = _rotl(x1, _ROT[(block % 2) * 4 + r])
            x1 = x1 ^ x0
        inj = block + 1
        x0 = x0 + ks[inj % 3]
        x1 = x1 + ks[(inj + 1) % 3] + np.uint32(inj)
    return x0, x1


def bits_to_open_unit(bits: jax.Array) -> jax.Array:
    """uint32 -> float32 in (0, 1), strictly positive so log() is finite.

    Mosaic has no uint32 -> float32 cast, so the word is converted as two exact
    16-bit int32 halves; ``hi·2¹⁶ + lo`` is rounded once, which is the same f32 a
    direct cast gives, so every consumer draws the values it always drew.
    """
    hi = (bits >> np.uint32(16)).astype(jnp.int32).astype(jnp.float32)
    lo = (bits & np.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return (hi * jnp.float32(65536.0) + lo + 0.5) * jnp.float32(2.0**-32)


def counter_normal(k0, k1, c0, c1, *, rounds: int | None = None):
    """One standard normal per counter pair via threefry + Box-Muller (cos branch).

    ``rounds=None`` resolves :func:`rng_rounds` (the ``REPRO_RNG_ROUNDS`` knob) —
    this is the one RNG call sited on the Gaussian hot path, so the reduced-round
    variant is scoped here.
    """
    b0, b1 = threefry2x32(k0, k1, c0, c1, rounds=rng_rounds() if rounds is None else rounds)
    u1 = bits_to_open_unit(b0)
    u2 = bits_to_open_unit(b1)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos(jnp.float32(2.0 * np.pi) * u2)


def key_to_words(key: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Extract the two uint32 words of a jax PRNG key."""
    data = jax.random.key_data(key).astype(jnp.uint32).reshape(-1)
    return data[0], data[1]


def keys_to_words(keys: jax.Array) -> jax.Array:
    """(q,)-batched PRNG keys -> (q, 2) uint32 words, row w == key_to_words(keys[w])."""
    return jax.random.key_data(keys).astype(jnp.uint32).reshape(keys.shape[0], 2)


def counter_rademacher(k0, k1, c0, c1, dtype=jnp.float32) -> jax.Array:
    """One ±1 sign per counter pair (low bit of the first threefry stream)."""
    b0, _ = threefry2x32(k0, k1, c0, c1)
    return (1 - 2 * (b0 & jnp.uint32(1)).astype(jnp.int32)).astype(dtype)


def packed_sign_words(k0, k1, rows: jax.Array, wcols: jax.Array) -> jax.Array:
    """One uint32 word of 32 packed Rademacher signs per (row, word-column) counter.

    The packed-sign contract shared by every consumer (jnp ``columns`` tiles, the
    Pallas Rademacher kernels): sign(i, j) = bit ``j % 32`` of
    ``threefry(key, i, j // 32)[0]`` — a pure function of (key, i, j), so any
    tiling / blocking / sharding regenerates the identical S. One threefry call
    yields 32 entries, versus one call *plus* Box-Muller per entry for the
    Gaussian stream — this is the whole RNG-bound-path fix.
    """
    b0, _ = threefry2x32(k0, k1, rows, wcols)
    return b0


def unpack_signs(words: jax.Array, bitpos: jax.Array, dtype=jnp.float32) -> jax.Array:
    """±1 from bit ``bitpos`` of each uint32 in ``words`` (shapes broadcast)."""
    bits = (words >> bitpos.astype(jnp.uint32)) & jnp.uint32(1)
    return (1 - 2 * bits.astype(jnp.int32)).astype(dtype)


def packed_sign_tile(k0, k1, row0, col0, nrows: int, ncols: int, dtype=jnp.float32) -> jax.Array:
    """Aligned packed-contract sign tile: ``col0`` (traced ok) and ``ncols`` must be
    multiples of 32 — the Pallas-kernel fast path (no covering slack, no slice)."""
    nw = ncols // 32
    rows = jnp.uint32(row0) + jax.lax.broadcasted_iota(jnp.uint32, (nrows, nw), 0)
    wcols = jnp.uint32(col0) // jnp.uint32(32) + jax.lax.broadcasted_iota(
        jnp.uint32, (nrows, nw), 1
    )
    words = jnp.repeat(packed_sign_words(k0, k1, rows, wcols), 32, axis=1)
    bitpos = jax.lax.broadcasted_iota(jnp.uint32, (nrows, ncols), 1) % jnp.uint32(32)
    return unpack_signs(words, bitpos, dtype)


def counter_rademacher_block(
    k0, k1, row0, col0, nrows: int, ncols: int, dtype=jnp.float32
) -> jax.Array:
    """(nrows, ncols) tile of ±1 packed-contract signs at (possibly traced) offsets.

    Draws the covering word range [col0//32, …] (``ncols // 32 + 2`` words per row
    — at most one wasted word each side for unaligned col0), unpacks, and
    dynamic-slices the requested window, so arbitrary ``block_rows`` streaming
    reproduces the aligned Pallas-kernel tiles bit-for-bit.
    """
    c0 = jnp.uint32(col0)
    w0 = c0 // jnp.uint32(32)
    nw = ncols // 32 + 2
    rows = jnp.uint32(row0) + jax.lax.broadcasted_iota(jnp.uint32, (nrows, nw), 0)
    wcols = w0 + jax.lax.broadcasted_iota(jnp.uint32, (nrows, nw), 1)
    words = jnp.repeat(packed_sign_words(k0, k1, rows, wcols), 32, axis=1)
    bitpos = jax.lax.broadcasted_iota(jnp.uint32, (nrows, nw * 32), 1) % jnp.uint32(32)
    signs = unpack_signs(words, bitpos, dtype)
    return jax.lax.dynamic_slice_in_dim(signs, (c0 - w0 * jnp.uint32(32)).astype(jnp.int32), ncols, axis=1)


def sjlt_counter_params(k0, k1, row_idx: jax.Array, s: int, m: int, dtype=jnp.float32):
    """SJLT buckets/signs for the given *global* row indices, counter-derived.

    Row ``i``'s parameters are a pure function of ``(key, i)`` — independent of
    how rows are blocked or which shard asks — so blocked/streamed application and
    the Pallas kernel all see the same S. Returns ``(buckets, signs)`` of shape
    ``(len(row_idx), s)`` with signs scaled by 1/√s (``E[SᵀS] = I``). Bucket ids use
    a modulo reduction of the uint32 stream; the bias is ≤ m·2⁻³² per draw.
    """
    r = row_idx.astype(jnp.uint32)[:, None]
    t = jnp.arange(s, dtype=jnp.uint32)[None, :]
    b0, b1 = threefry2x32(k0, k1, r, t)
    buckets = (b0 % jnp.uint32(m)).astype(jnp.int32)
    signs = (1 - 2 * (b1 & jnp.uint32(1)).astype(jnp.int32)).astype(dtype)
    return buckets, signs * jnp.asarray(1.0 / np.sqrt(s), dtype)


@functools.lru_cache(maxsize=None)
def _hadamard_cached(k: int, dtype_name: str) -> np.ndarray:
    # Host-side cache: a device jnp array must NOT be cached here, or the first
    # call under a jit trace would leak its tracer into every later trace.
    i = np.arange(k)[:, None] & np.arange(k)[None, :]
    signs = 1 - 2 * (np.bitwise_count(i.astype(np.uint64)).astype(np.int32) & 1)
    return np.asarray(signs, dtype=np.dtype(dtype_name))


def hadamard_matrix(k: int, dtype=jnp.float32) -> jax.Array:
    """Unnormalized k×k Hadamard (Sylvester): H[i,j] = (-1)^popcount(i&j), k pow2.

    Cached on (k, dtype): every SRHT apply/gram trace uses the same one or two
    factor matrices, and the O(k²) popcount construction was being repaid per
    trace. The conversion per call is a cheap constant embed / transfer.
    """
    if k & (k - 1):
        raise ValueError(f"Hadamard size must be a power of two, got {k}")
    return jnp.asarray(_hadamard_cached(k, np.dtype(dtype).name))


def pad_axis_to(x: jax.Array, axis: int, target: int) -> jax.Array:
    if x.shape[axis] == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, pads)


def round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
