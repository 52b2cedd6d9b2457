"""Local least-squares / least-norm solvers and sketch-and-solve (Algorithm 1 worker).

The worker-side problem is tiny (m×d with m = O(d)), so direct dense factorizations are
the right tool; CG is provided for the ill-conditioned / regularized path and as the
building block of the iterative-Hessian-sketch baseline.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import operators, sketches as sk

# ``jax.named_scope`` of the d×d Cholesky tail's ops, in every path that solves a Gram.
SOLVE_TAIL_SCOPE = "repro.solve_tail"


# --------------------------------------------------------------------------- direct


def lstsq(A: jax.Array, b: jax.Array, *, reg: float = 0.0, method: str = "qr") -> jax.Array:
    """argmin_x ‖Ax − b‖² + reg·‖x‖², A: (n, d), b: (n,) or (n, k)."""
    if method == "qr":
        if reg > 0.0:
            d = A.shape[1]
            A_aug = jnp.concatenate([A, jnp.sqrt(reg) * jnp.eye(d, dtype=A.dtype)], axis=0)
            b_aug = jnp.concatenate(
                [b, jnp.zeros((d,) + b.shape[1:], dtype=b.dtype)], axis=0
            )
            A, b = A_aug, b_aug
        Q, R = jnp.linalg.qr(A)
        return jax.scipy.linalg.solve_triangular(R, Q.T @ b, lower=False)
    if method == "chol":
        d = A.shape[1]
        G = A.T @ A + reg * jnp.eye(d, dtype=A.dtype)
        c = A.T @ b
        L = jnp.linalg.cholesky(G)
        y = jax.scipy.linalg.solve_triangular(L, c, lower=True)
        return jax.scipy.linalg.solve_triangular(L.T, y, lower=False)
    if method == "cg":
        return _cg_normal(A, b, reg=reg)
    raise ValueError(f"unknown method {method!r}")


def _cg_normal(A: jax.Array, b: jax.Array, *, reg: float = 0.0, iters: int = 64) -> jax.Array:
    """CG on the normal equations (AᵀA + reg·I)x = Aᵀb. Matrix-free."""

    def mv(x):
        return A.T @ (A @ x) + reg * x

    rhs = A.T @ b
    x0 = jnp.zeros_like(rhs)

    def body(_, state):
        x, r, p, rs = state
        Ap = mv(p)
        alpha = rs / (jnp.vdot(p, Ap) + 1e-30)
        x = x + alpha * p
        r = r - alpha * Ap
        rs_new = jnp.vdot(r, r)
        p = r + (rs_new / (rs + 1e-30)) * p
        return x, r, p, rs_new

    r0 = rhs - mv(x0)
    state = (x0, r0, r0, jnp.vdot(r0, r0))
    x, *_ = jax.lax.fori_loop(0, iters, body, state)
    return x


def lstsq_gram(G: jax.Array, c: jax.Array, *, reg: float = 0.0) -> jax.Array:
    """Solve ``(G + reg·I) x = c`` by Cholesky — the tiny d×d tail of the fused path.

    ``(G, c) = ((SA)ᵀ(SA), (SA)ᵀ(Sb))`` come out of one streamed sketch→Gram pass
    (:meth:`repro.core.operators.SketchOp.gram_blocked`); nothing here ever sees SA.
    """
    d = G.shape[0]
    with jax.named_scope(SOLVE_TAIL_SCOPE):
        L = jnp.linalg.cholesky(G + reg * jnp.eye(d, dtype=G.dtype))
        y = jax.scipy.linalg.solve_triangular(L, c, lower=True)
        return jax.scipy.linalg.solve_triangular(L.T, y, lower=False)


def least_norm(A: jax.Array, b: jax.Array) -> jax.Array:
    """min ‖x‖² s.t. Ax = b (n < d, full row rank): x = Aᵀ(AAᵀ)⁻¹b."""
    G = A @ A.T
    L = jnp.linalg.cholesky(G)
    y = jax.scipy.linalg.solve_triangular(L, b, lower=True)
    z = jax.scipy.linalg.solve_triangular(L.T, y, lower=False)
    return A.T @ z


# ----------------------------------------------------------------- sketch-and-solve


def sketch_and_solve(
    spec: sk.SketchSpec,
    key: jax.Array,
    A: jax.Array,
    b: jax.Array,
    *,
    reg: float = 0.0,
    method: str = "fused",
    block_rows: int = operators.DEFAULT_BLOCK_ROWS,
) -> jax.Array:
    """One worker of Algorithm 1 (left sketch, n > d):
    x̂ = argmin_x ‖S(Ax − b)‖² with S ~ spec.

    ``method="fused"`` (default) takes the single-pass sketch→Gram fast path:
    ``(G, c)`` accumulate in one streamed pass over ``[A | b]`` — SA is never
    materialized — and the solve is a d×d Cholesky. The two-pass paths
    (``"qr"``/``"chol"``/``"cg"``: materialize (SA, Sb), then factorize) are
    retained as the reference oracle.
    """
    if method == "fused":
        G, c = operators.gram_blocked(spec, key, A, b, block_rows=block_rows)
        return lstsq_gram(G, c, reg=reg)
    SA, Sb = sk.sketch_data(spec, key, A, b)
    return lstsq(SA, Sb, reg=reg, method=method)


def sketch_least_norm(
    spec: sk.SketchSpec,
    key: jax.Array,
    A: jax.Array,
    b: jax.Array,
) -> jax.Array:
    """One worker of the right-sketch least-norm problem (§V, n < d):
    ẑ = argmin ‖z‖² s.t. (ASᵀ)z = b;  x̂ = Sᵀẑ.

    S never exists in memory: ``ASᵀ = (S Aᵀ)ᵀ`` is one forward application of the
    operator to Aᵀ, and ``Sᵀẑ`` is its adjoint — a scatter for sampling sketches, an
    inverse-transform for SRHT, streamed counter-RNG tiles for Gaussian.
    """
    d = A.shape[1]
    # Data-independent right sketches only; a leverage right-sketch of I_d is uniform.
    scores = jnp.ones((d,), A.dtype) if spec.kind == "leverage" else None
    op = operators.make_operator(spec, key, d, scores=scores)
    SAt = op.apply(A.T)  # (m, n) = S @ Aᵀ
    z = least_norm(SAt.T, b)  # (m,) or (m, k)
    return op.adjoint(z)


def residual_cost(A: jax.Array, b: jax.Array, x: jax.Array) -> jax.Array:
    """f(x) = ‖Ax − b‖², with ``Ax`` at f32 precision: the residual is a small
    difference of large terms, and a bf16-pass product (the TPU default) would
    swamp the excess cost the paper's error metric measures."""
    r = jnp.matmul(A, x, precision=jax.lax.Precision.HIGHEST) - b
    return jnp.vdot(r, r).real


def relative_error(A, b, x, fstar) -> jax.Array:
    """(f(x) − f(x*)) / f(x*) — the paper's 'approximation error'."""
    return (residual_cost(A, b, x) - fstar) / fstar
