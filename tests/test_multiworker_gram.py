"""Multi-worker fused Gram kernels, the Rademacher family, counter-RNG knobs, and
the host-streamed out-of-core Gram.

The contract under test: ``gram_batched`` on a kernel-routed spec takes ONE
multi-worker Pallas launch whose per-worker slices are *bitwise identical* to the
q-launch per-key loop — same padding, same tile walk, same op sequence per worker.
Everything downstream (master-sketch mode, IHS) then switches paths for free.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import operators as ops, sketches as sk
from repro.kernels import common as kcommon
from repro.utils import prng

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KERNEL_KINDS = ["gaussian", "rademacher", "sjlt", "srht"]

# Odd n, not divisible by any kernel row tile; exercises the padded last tile.
N, D, M, Q = 201, 6, 24, 3


def _spec(kind, m=M, use_kernel=True):
    if kind == "sjlt":
        return sk.SketchSpec(kind, m, s=3, use_kernel=use_kernel)
    return sk.SketchSpec(kind, m, use_kernel=use_kernel)


@pytest.mark.parametrize("kind", KERNEL_KINDS)
@pytest.mark.parametrize("with_b", [True, False])
def test_fused_multi_bitwise_matches_per_worker_loop(kind, with_b):
    """gram_batched's one-launch path == q per-key kernel launches, bitwise."""
    A = jax.random.normal(jax.random.PRNGKey(0), (N, D))
    b = jax.random.normal(jax.random.PRNGKey(1), (N,)) if with_b else None
    keys = prng.worker_keys(jax.random.PRNGKey(2), Q)
    spec = _spec(kind)
    Gs, cs = ops.gram_batched(spec, keys, A, b)
    assert Gs.shape == (Q, D, D)
    for w in range(Q):
        Gw, cw = ops.make_operator(spec, keys[w], N).gram_blocked(A, b)
        np.testing.assert_array_equal(np.asarray(Gs[w]), np.asarray(Gw), err_msg=kind)
        if with_b:
            np.testing.assert_array_equal(np.asarray(cs[w]), np.asarray(cw), err_msg=kind)
        else:
            assert cs is None


def test_fused_multi_matrix_b():
    """Multi-target b (n, k) rides through the fused multi launch unchanged."""
    A = jax.random.normal(jax.random.PRNGKey(0), (N, D))
    b = jax.random.normal(jax.random.PRNGKey(1), (N, 2))
    keys = prng.worker_keys(jax.random.PRNGKey(2), Q)
    spec = _spec("rademacher")
    Gs, cs = ops.gram_batched(spec, keys, A, b)
    assert cs.shape == (Q, D, 2)
    for w in range(Q):
        Gw, cw = ops.make_operator(spec, keys[w], N).gram_blocked(A, b)
        np.testing.assert_array_equal(np.asarray(Gs[w]), np.asarray(Gw))
        np.testing.assert_array_equal(np.asarray(cs[w]), np.asarray(cw))


def test_gram_batched_kernel_base_returns_notimplemented():
    """Kinds without a multi-worker kernel have none by class (no run-time
    fallback): the base and the sampling kinds carry ``None`` and take per-key
    dispatch; the four kernel families carry the kernel."""
    assert ops.SketchOp.gram_batched_kernel is None
    assert ops.make_operator(sk.SketchSpec("uniform", M), jax.random.PRNGKey(0), N).gram_batched_kernel is None
    for kind in KERNEL_KINDS:
        assert ops._REGISTRY[kind].gram_batched_kernel is not None, kind
    # ... and gram_batched still works for them with use_kernel-less specs.
    A = jax.random.normal(jax.random.PRNGKey(0), (N, D))
    keys = prng.worker_keys(jax.random.PRNGKey(2), Q)
    Gs, cs = ops.gram_batched(sk.SketchSpec("uniform", M), keys, A)
    assert Gs.shape == (Q, D, D) and cs is None


def test_one_device_mesh_takes_the_multi_worker_kernel(monkeypatch):
    """A mesh of one worker shard (one chip) shards nothing, so gram_batched keeps
    the one-launch kernel even where mesh batching is on."""
    from repro.launch.mesh import make_mesh

    monkeypatch.setenv("REPRO_MESH_BATCH", "1")
    calls = []
    cls = ops._REGISTRY["gaussian"]
    fused = cls.gram_batched_kernel
    monkeypatch.setattr(cls, "gram_batched_kernel", lambda *a: calls.append(1) or fused(*a))
    A = jax.random.normal(jax.random.PRNGKey(0), (N, D))
    keys = prng.worker_keys(jax.random.PRNGKey(2), Q)
    mesh = make_mesh((1,), ("workers",))
    Gs, _ = ops.gram_batched(_spec("gaussian"), keys, A, mesh=mesh, axis_names=("workers",))
    assert calls == [1] and Gs.shape == (Q, D, D)
    assert not ops._mesh_shards_keys(mesh, ("workers",), Q)


# ------------------------------------------------------------- rademacher family


def test_rademacher_columns_match_materialized_tile():
    """The streamed columns() window (covering-word unpack at arbitrary offsets)
    == the same slice of the materialized packed-contract S."""
    op = ops.make_operator(sk.SketchSpec("rademacher", M), jax.random.PRNGKey(5), N)
    S = np.asarray(op.materialize())
    for j0, block in [(0, 32), (7, 40), (33, 64), (160, 41)]:
        tile = np.asarray(op.columns(jnp.int32(j0), block))
        np.testing.assert_array_equal(tile[:, : N - j0], S[:, j0 : j0 + block][:, : N - j0])


def test_rademacher_signs_are_packed_bits():
    """sign(i, j) = bit j%32 of threefry(key, i, j//32)[0] — the packed contract
    every consumer (jnp, kernels) shares."""
    k0, k1 = kcommon.key_to_words(jax.random.PRNGKey(5))
    rows = jnp.arange(8, dtype=jnp.uint32)[:, None]
    words = kcommon.packed_sign_words(k0, k1, rows, jnp.uint32(0))
    signs = np.asarray(
        kcommon.counter_rademacher_block(k0, k1, jnp.uint32(0), jnp.uint32(0), 8, 32)
    )
    for j in range(32):
        expect = 1.0 - 2.0 * ((np.asarray(words)[:, 0] >> j) & 1)
        np.testing.assert_array_equal(signs[:, j], expect)


def test_rademacher_kernel_sketch_matches_oracle():
    from repro.kernels.rademacher import ops as rops, ref as rref

    n, d, m = 150, 5, 40
    A = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    key = jax.random.PRNGKey(3)
    got = rops.rademacher_sketch(key, A, m)
    want = rref.rademacher_sketch(key, A, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_rademacher_unbiased_gram():
    """E[SᵀS] = I for the packed family: averaged Gram of S·I approaches I."""
    n, m, reps = 32, 64, 48
    keys = prng.worker_keys(jax.random.PRNGKey(9), reps)
    I = jnp.eye(n)
    spec = sk.SketchSpec("rademacher", m)
    acc = sum(np.asarray(G) for G in
              jax.vmap(lambda k: ops.gram_blocked(spec, k, I)[0])(keys))
    np.testing.assert_allclose(acc / reps, np.eye(n), atol=0.15)


# ------------------------------------------------------------ RNG rounds knob


def test_threefry_20_rounds_matches_inline_oracle():
    """The hand-rolled threefry2x32 at the default 20 rounds == an independent
    numpy transcription of the Salmon et al. reference."""

    def oracle(k0, k1, c0, c1):
        R = [[13, 15, 26, 6], [17, 29, 16, 24]]
        ks = [np.uint32(k0), np.uint32(k1), np.uint32(k0 ^ k1 ^ np.uint32(0x1BD11BDA))]
        x = [np.uint32(c0 + ks[0]), np.uint32(c1 + ks[1])]
        for block in range(5):
            for r in R[block % 2]:
                x[0] = np.uint32(x[0] + x[1])
                x[1] = np.uint32((np.uint32(x[1] << r) | np.uint32(x[1] >> (32 - r))))
                x[1] = np.uint32(x[0] ^ x[1])
            x[0] = np.uint32(x[0] + ks[(block + 1) % 3])
            x[1] = np.uint32(x[1] + ks[(block + 2) % 3] + np.uint32(block + 1))
        return x

    old = np.seterr(over="ignore")
    try:
        for k0, k1, c0, c1 in [(1, 2, 3, 4), (0, 0, 0, 0), (2**32 - 1, 7, 2**31, 5)]:
            b0, b1 = kcommon.threefry2x32(
                jnp.uint32(k0), jnp.uint32(k1), jnp.uint32(c0), jnp.uint32(c1)
            )
            w0, w1 = oracle(k0, k1, c0, c1)
            assert int(b0) == int(w0) and int(b1) == int(w1), (k0, k1, c0, c1)
    finally:
        np.seterr(**old)


def test_rng_rounds_default_and_validation():
    assert kcommon.rng_rounds() == kcommon.DEFAULT_ROUNDS == 20
    c = jnp.uint32(3)
    z_def = kcommon.counter_normal(jnp.uint32(1), jnp.uint32(2), c, c)
    z_20 = kcommon.counter_normal(jnp.uint32(1), jnp.uint32(2), c, c, rounds=20)
    assert float(z_def) == float(z_20)
    assert float(kcommon.counter_normal(jnp.uint32(1), jnp.uint32(2), c, c, rounds=8)) != float(
        z_20
    )


def _run_subprocess(body: str, env_extra: dict, timeout: int = 900) -> str:
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"), **env_extra)
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


@pytest.mark.subprocess
def test_reduced_rounds_env_knob():
    """REPRO_RNG_ROUNDS=8 (resolved at trace time, hence the subprocess): the
    gaussian kernel and jnp paths stay mutually consistent — they share the
    reduced-round stream — while the stream itself departs from the default."""
    out = _run_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import operators as ops, sketches as sk
        from repro.kernels import common as kcommon

        assert kcommon.rng_rounds() == 8
        n, d, m = 160, 6, 24
        A = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        key = jax.random.PRNGKey(9)
        G_k, _ = ops.make_operator(sk.SketchSpec("gaussian", m, use_kernel=True), key, n).gram_blocked(A)
        G_j, _ = ops.make_operator(sk.SketchSpec("gaussian", m), key, n).gram_blocked(A)
        np.testing.assert_allclose(np.asarray(G_k), np.asarray(G_j), rtol=1e-3, atol=1e-3)
        c = jnp.uint32(3)
        z8 = kcommon.counter_normal(jnp.uint32(1), jnp.uint32(2), c, c)
        z20 = kcommon.counter_normal(jnp.uint32(1), jnp.uint32(2), c, c, rounds=20)
        assert float(z8) != float(z20)
        print("ROUNDS8_OK")
        """,
        {"REPRO_RNG_ROUNDS": "8"},
    )
    assert "ROUNDS8_OK" in out


@pytest.mark.subprocess
def test_invalid_rounds_rejected():
    out = _run_subprocess(
        """
        from repro.kernels import common as kcommon
        for bad in ("6", "0", "-4", "x"):
            import os
            os.environ["REPRO_RNG_ROUNDS"] = bad
            try:
                kcommon.rng_rounds()
            except ValueError:
                pass
            else:
                raise SystemExit(f"accepted bad rounds {bad!r}")
        print("VALIDATION_OK")
        """,
        {},
    )
    assert "VALIDATION_OK" in out


# ------------------------------------------------------------- host-streamed gram


@pytest.mark.parametrize("kind", ["gaussian", "rademacher", "sjlt", "uniform"])
def test_gram_blocked_host_matches_device(kind):
    """Host-streamed out-of-core Gram == the on-device streamed Gram for block
    sizes that do not divide n, with and without b."""
    A = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (N, D)))
    b = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (N,)))
    key = jax.random.PRNGKey(4)
    spec = _spec(kind, use_kernel=False)
    op = ops.make_operator(spec, key, N)
    for b_ in (b, None):
        Gh, ch = ops.gram_blocked_host(spec, key, A, b_, block_rows=64)
        Gd, cd = op.gram_blocked(jnp.asarray(A), None if b_ is None else jnp.asarray(b_),
                                 block_rows=64)
        np.testing.assert_allclose(np.asarray(Gh), np.asarray(Gd), rtol=1e-4, atol=1e-4)
        if b_ is None:
            assert ch is None and cd is None
        else:
            np.testing.assert_allclose(np.asarray(ch), np.asarray(cd), rtol=1e-4, atol=1e-4)


def test_gram_blocked_host_memmap(tmp_path):
    """np.memmap input: the stream never loads all of A — the shipping case."""
    A = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (N, D)), np.float32)
    path = tmp_path / "A.bin"
    mm = np.memmap(path, dtype=np.float32, mode="w+", shape=(N, D))
    mm[:] = A
    mm.flush()
    ro = np.memmap(path, dtype=np.float32, mode="r", shape=(N, D))
    spec = sk.SketchSpec("rademacher", M)
    key = jax.random.PRNGKey(4)
    Gm, _ = ops.gram_blocked_host(spec, key, ro, block_rows=50)
    Ga, _ = ops.gram_blocked_host(spec, key, A, block_rows=50)
    np.testing.assert_array_equal(np.asarray(Gm), np.asarray(Ga))


def test_gram_blocked_host_single_tile():
    """block_rows >= n: one tile, no prefetch loop — still correct."""
    A = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (64, D)))
    spec = sk.SketchSpec("gaussian", M)
    key = jax.random.PRNGKey(4)
    Gh, _ = ops.gram_blocked_host(spec, key, A, block_rows=4096)
    Gd, _ = ops.make_operator(spec, key, 64).gram_blocked(jnp.asarray(A))
    np.testing.assert_allclose(np.asarray(Gh), np.asarray(Gd), rtol=1e-5, atol=1e-5)


# -------------------------------------------------------------- misc satellites


def test_hadamard_matrix_cached():
    """The host-side popcount construction is cached per (k, dtype), and calling
    under a jit trace must not poison the cache with a leaked tracer."""
    assert kcommon._hadamard_cached(16, "float32") is kcommon._hadamard_cached(16, "float32")
    assert isinstance(kcommon._hadamard_cached(16, "float32"), np.ndarray)
    H = kcommon.hadamard_matrix(16, jnp.float32)
    np.testing.assert_array_equal(np.asarray(H).T @ np.asarray(H), 16 * np.eye(16))
    traced = jax.jit(lambda: kcommon.hadamard_matrix(16, jnp.float32))()
    np.testing.assert_array_equal(np.asarray(traced), np.asarray(H))
    post = kcommon.hadamard_matrix(16, jnp.float32)  # after a trace: still concrete
    np.testing.assert_array_equal(np.asarray(post), np.asarray(H))
    with pytest.raises(ValueError):
        kcommon.hadamard_matrix(12, jnp.float32)


def test_prng_reexports_are_kernel_common():
    """utils.prng re-exports the single source of truth in kernels.common."""
    assert prng.bits_to_open_unit is kcommon.bits_to_open_unit
    assert prng.counter_normal is kcommon.counter_normal
    assert prng.counter_rademacher is kcommon.counter_rademacher
    assert prng.counter_rademacher_block is kcommon.counter_rademacher_block
