"""Fused single-pass sketch→Gram pipeline: oracle equivalence and batching paths.

The fused path never materializes SA — every test here checks it against the
two-pass reference (materialize S, form (SA)ᵀ(SA) densely) or against the
loop fallback under shared worker keys.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import operators as ops, sketches as sk, solve
from repro.utils import prng

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N, D, M = 100, 7, 24  # N not a power of two / multiple of the block sizes below


def _op(kind, key, n=N, m=M, use_kernel=False):
    if kind == "hybrid":
        spec = sk.SketchSpec("hybrid", m, m_prime=min(2 * m, n), inner="sjlt", s=2)
    elif kind == "sjlt":
        spec = sk.SketchSpec(kind, m, s=3, use_kernel=use_kernel)
    elif kind == "uniform":
        spec = sk.SketchSpec(kind, m, replacement=False)
    else:
        spec = sk.SketchSpec(kind, m, use_kernel=use_kernel)
    scores = None
    if kind == "leverage":
        A = jax.random.normal(jax.random.PRNGKey(7), (n, 5))
        scores = sk.leverage_scores(A)
    return ops.make_operator(spec, key, n, scores=scores)


def _oracle(op, A, b):
    """Two-pass reference: explicit S, dense SA, dense Gram."""
    S = np.asarray(op.materialize(), np.float64)
    SA = S @ np.asarray(A, np.float64)
    Sb = S @ np.asarray(b, np.float64)
    return SA.T @ SA, SA.T @ Sb


@pytest.mark.parametrize("kind", sk.KINDS)
@pytest.mark.parametrize("block_rows", [33, 96])
def test_gram_blocked_matches_materialized_oracle(kind, block_rows):
    """(G, c) from the fused streamed pass == (SA)ᵀ(SA), (SA)ᵀ(Sb) for every
    registered kind and block sizes that do not divide n."""
    op = _op(kind, jax.random.PRNGKey(3))
    A = jax.random.normal(jax.random.PRNGKey(0), (N, D))
    b = jax.random.normal(jax.random.PRNGKey(1), (N,))
    G, c = op.gram_blocked(A, b, block_rows=block_rows)
    G_ref, c_ref = _oracle(op, A, b)
    assert G.shape == (D, D) and c.shape == (D,)
    np.testing.assert_allclose(np.asarray(G), G_ref, rtol=2e-3, atol=1e-3, err_msg=kind)
    np.testing.assert_allclose(np.asarray(c), c_ref, rtol=2e-3, atol=1e-3, err_msg=kind)


@pytest.mark.parametrize("kind", ["gaussian", "rademacher", "srht", "sjlt"])
def test_kernel_gram_matches_materialized_oracle(kind):
    """The fully fused Pallas kernels (S generated in-core, accumulator in VMEM
    scratch) reproduce the dense two-pass Gram."""
    n, d, m = 200, 9, 32
    op = _op(kind, jax.random.PRNGKey(5), n=n, m=m, use_kernel=True)
    A = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    b = jax.random.normal(jax.random.PRNGKey(1), (n, 2))
    G, c = op.gram_blocked(A, b)
    G_ref, c_ref = _oracle(op, A, b)
    assert G.shape == (d, d) and c.shape == (d, 2)
    np.testing.assert_allclose(np.asarray(G), G_ref, rtol=2e-3, atol=1e-3, err_msg=kind)
    np.testing.assert_allclose(np.asarray(c), c_ref, rtol=2e-3, atol=1e-3, err_msg=kind)


@pytest.mark.parametrize("kind", ["gaussian", "rademacher", "srht", "sjlt"])
def test_kernel_gram_matches_jnp_gram(kind):
    """use_kernel=True and the jnp streaming path draw the same counter-based S,
    so their Grams agree to float tolerance."""
    n, d, m = 160, 6, 24
    A = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    key = jax.random.PRNGKey(9)
    G_k, _ = _op(kind, key, n=n, m=m, use_kernel=True).gram_blocked(A)
    G_j, _ = _op(kind, key, n=n, m=m, use_kernel=False).gram_blocked(A)
    np.testing.assert_allclose(np.asarray(G_k), np.asarray(G_j), rtol=1e-3, atol=1e-3)


def test_gram_blocked_without_b_returns_none_c():
    op = _op("gaussian", jax.random.PRNGKey(3))
    A = jax.random.normal(jax.random.PRNGKey(0), (N, D))
    G, c = op.gram_blocked(A)
    assert c is None and G.shape == (D, D)


def test_gaussian_adjoint_kernel_matches_jnp():
    """The new Gaussian adjoint kernel (matrix-free Sᵀ) == the counter-RNG jnp path."""
    n, m, k = 137, 48, 3
    key = jax.random.PRNGKey(4)
    Y = jax.random.normal(jax.random.PRNGKey(1), (m, k))
    out_k = ops.make_operator(sk.SketchSpec("gaussian", m, use_kernel=True), key, n).adjoint(Y)
    out_j = ops.make_operator(sk.SketchSpec("gaussian", m), key, n).adjoint(Y)
    assert out_k.shape == (n, k)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_j), rtol=1e-4, atol=1e-4)


def test_sketch_least_norm_kernel_path_matrix_free():
    """Right-sketch least-norm with use_kernel=True stays matrix-free end to end
    (kernel forward + the new adjoint kernel) and matches the jnp path."""
    n, d = 12, 64
    A = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    b = jax.random.normal(jax.random.PRNGKey(1), (n,))
    key = jax.random.PRNGKey(2)
    x_k = solve.sketch_least_norm(sk.SketchSpec("gaussian", 4 * n, use_kernel=True), key, A, b)
    x_j = solve.sketch_least_norm(sk.SketchSpec("gaussian", 4 * n), key, A, b)
    np.testing.assert_allclose(np.asarray(x_k), np.asarray(x_j), rtol=1e-3, atol=1e-4)


def test_double_buffered_scan_matches_reference():
    """The double-buffered row-tile scan == the plain reshape-scan reference."""
    A = jax.random.normal(jax.random.PRNGKey(0), (N, D))
    init = jnp.zeros((D,), jnp.float32)
    reducer = lambda acc, j0, Ab: acc + jnp.sum(Ab, axis=0) * (1.0 + 0.01 * j0)
    got = ops._scan_row_blocks(A, N, 33, init, reducer, double_buffer=True)
    want = ops._scan_row_blocks(A, N, 33, init, reducer, double_buffer=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_sketch_and_solve_matches_qr_oracle():
    """method='fused' (default) solves the same sketched problem as the two-pass
    QR reference under the same key."""
    n, d, m = 1024, 12, 96
    A = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    b = jax.random.normal(jax.random.PRNGKey(1), (n,))
    key = jax.random.PRNGKey(2)
    for spec in (sk.SketchSpec("gaussian", m), sk.SketchSpec("sjlt", m, s=3)):
        x_f = solve.sketch_and_solve(spec, key, A, b)
        x_qr = solve.sketch_and_solve(spec, key, A, b, method="qr")
        np.testing.assert_allclose(np.asarray(x_f), np.asarray(x_qr), rtol=2e-3, atol=2e-4)


def test_gram_batched_matches_per_key_gram():
    """gram_batched == a Python loop of per-key gram_blocked calls."""
    q = 4
    A = jax.random.normal(jax.random.PRNGKey(0), (N, D))
    b = jax.random.normal(jax.random.PRNGKey(1), (N,))
    spec = sk.SketchSpec("gaussian", M)
    keys = prng.worker_keys(jax.random.PRNGKey(2), q)
    Gs, cs = ops.gram_batched(spec, keys, A, b)
    assert Gs.shape == (q, D, D) and cs.shape == (q, D)
    for w in range(q):
        Gw, cw = ops.gram_blocked(spec, keys[w], A, b)
        np.testing.assert_allclose(np.asarray(Gs[w]), np.asarray(Gw), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(cs[w]), np.asarray(cw), rtol=1e-5, atol=1e-5)


def _run_subprocess(body: str, devices: int = 8, timeout: int = 900) -> str:
    script = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        "os.environ['REPRO_MESH_BATCH'] = '1'  # force the mesh path on fake devices\n"
        + textwrap.dedent(body)
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, timeout=timeout, env=env
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


@pytest.mark.subprocess
def test_apply_batched_mesh_matches_loop_bitwise():
    """shard_map-over-mesh apply_batched == the loop fallback, bitwise, under the
    same worker keys (each shard runs a lax.map over its block of keys — the exact
    computation the fallback runs over all of them)."""
    _run_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import operators as ops, sketches as sk
        from repro.utils import prng

        n, d, m, q = 512, 8, 64, 8
        A = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        keys = prng.worker_keys(jax.random.PRNGKey(1), q)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("workers",))
        for spec in (sk.SketchSpec("srht", m), sk.SketchSpec("gaussian", m)):
            meshed = ops.apply_batched(spec, keys, A, mesh=mesh, axis_names=("workers",))
            looped_ref = jax.lax.map(lambda k: ops.apply(spec, k, A), keys)
            np.testing.assert_array_equal(np.asarray(meshed), np.asarray(looped_ref))
            # the auto-dispatched no-mesh path (vmap or loop) agrees to float tol
            auto = ops.apply_batched(spec, keys, A)
            np.testing.assert_allclose(
                np.asarray(auto), np.asarray(looped_ref), rtol=1e-5, atol=1e-5
            )
        print("MESH_OK")
        """
    )


@pytest.mark.subprocess
def test_gram_batched_mesh_matches_loop():
    """Mesh-parallel gram_batched (what master-sketch mode ships) == loop path."""
    _run_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import operators as ops, sketches as sk
        from repro.utils import prng

        n, d, m, q = 512, 8, 64, 8
        A = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        b = jax.random.normal(jax.random.PRNGKey(2), (n,))
        keys = prng.worker_keys(jax.random.PRNGKey(1), q)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("workers",))
        spec = sk.SketchSpec("gaussian", m)
        Gs_m, cs_m = ops.gram_batched(spec, keys, A, b, mesh=mesh, axis_names=("workers",))
        Gs_l, cs_l = ops.gram_batched(spec, keys, A, b)
        np.testing.assert_allclose(np.asarray(Gs_m), np.asarray(Gs_l), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(cs_m), np.asarray(cs_l), rtol=1e-4, atol=1e-4)
        print("GRAM_MESH_OK")
        """
    )
