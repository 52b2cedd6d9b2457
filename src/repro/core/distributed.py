"""Distributed sketch-and-solve over a JAX mesh (the paper's Algorithm 1 at pod scale).

The q serverless workers become shards of one (or more) mesh axes. Each shard:
  1. derives its own key (fold_in worker index) — workers are stateless i.i.d. copies,
  2. sketches (A, b) → (S_kA, S_kb)   [master-sketch mode ships these; worker-sketch
     mode computes them from replicated/broadcast A],
  3. solves the m×d sub-problem locally,
  4. contributes to a masked psum average (stragglers contribute 0 and shrink the
     denominator — the estimator is Algorithm 1 with the realized q′).

Two data-placement regimes:
  * ``replicated``   — every worker sees all of A (the paper's setting; A replicated or
    broadcast once, privacy mode has the master do step 2).
  * ``row_sharded``  — beyond-paper: A is row-sharded across workers and each worker
    sketches only its own rows (sampling-family sketches restricted to the local block,
    scaled by the global n). The average is then over *local-block* estimators; this is
    the divide-and-conquer ("local sketching") regime — biased in general but it never
    moves raw rows across hosts, and for uniform-sampling sketches it is *identical in
    distribution* to global uniform sampling when rows are exchangeable.

All-straggler contract (shared by every solve variant here): a *concrete* mask with
zero survivors raises ``ValueError`` eagerly — an empty round has no estimator and is
a caller bug; a *traced* mask (the mask computed inside a jitted step) NaN-poisons x̄
by default, with ``on_empty="zero"`` restoring the legacy silent x̄ = 0.

These mesh drivers are the *synchronous idealization* — every worker launches at
once and the mask is known up front. The asynchronous reality (arrival order,
deadlines, retries, early stopping) lives in :mod:`repro.runtime`;
:func:`distributed_sketch_solve_multiround` delegates to it when given a
``latency`` model.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import averaging, operators, sketches as sk, solve
from repro.utils import prng

# Every worker body below may run a Pallas kernel (``spec.use_kernel``), whose
# outputs carry no varying-mesh-axis type; the shard_maps therefore run with
# ``check_vma=False``.


_worker_index = averaging.worker_index

# Incremented each time the multiround worker body is traced; tests assert the
# jitted closure is hoisted out of the round loop (one trace per call, not per round).
MULTIROUND_TRACE_COUNT = 0


def _checked_mask(straggler_mask: Optional[jax.Array], q: int) -> jax.Array:
    """Default / validate the straggler mask; raise eagerly on a concrete empty round."""
    if straggler_mask is None:
        return jnp.ones((q,), jnp.float32)
    if not isinstance(straggler_mask, jax.core.Tracer):
        arr = np.asarray(straggler_mask)
        if arr.sum() == 0:
            raise ValueError(
                "straggler_mask has no surviving workers (q' = 0): the Algorithm-1 "
                "average over an empty set is undefined. Loosen the deadline or "
                "resubmit the round (see repro.runtime for retries)."
            )
    return straggler_mask


def distributed_sketch_solve(
    mesh: Mesh,
    spec: sk.SketchSpec,
    key: jax.Array,
    A: jax.Array,
    b: jax.Array,
    *,
    axis_names: tuple = ("data",),
    reg: float = 0.0,
    method: str = "fused",
    straggler_mask: Optional[jax.Array] = None,
    row_sharded: bool = False,
    round_id: int = 0,
    on_empty: str = "nan",
):
    """Algorithm 1 over ``mesh``: one sketch-and-solve worker per shard of axis_names.

    Each worker takes the fused single-pass sketch→Gram path by default
    (``method="fused"`` in :func:`repro.core.solve.sketch_and_solve`): it streams
    its (G_k, c_k) out of one pass over the local copy of [A | b] and solves d×d,
    never materializing S_kA. Pass ``method="qr"`` for the two-pass reference.

    Args:
      straggler_mask: optional (q,) float mask of which workers made the deadline
        (1=arrived). None = all arrived. A concrete all-zero mask raises eagerly.
      row_sharded: shard A's rows over the worker axes instead of replicating.
      on_empty: traced-mask q'=0 behavior — ``"nan"`` (default) or ``"zero"``.
    Returns:
      x̄ (d,), replicated.
    """
    q = operators.mesh_world(mesh, axis_names)
    straggler_mask = _checked_mask(straggler_mask, q)

    a_spec = P(axis_names) if row_sharded else P()
    in_specs = (P(), a_spec, P(), P())
    out_specs = P()

    def worker(key, A_blk, b_blk, mask_all):
        widx = _worker_index(axis_names)
        wkey = prng.worker_key(key, widx, round_id)
        xk = solve.sketch_and_solve(spec, wkey, A_blk, b_blk, reg=reg, method=method)
        return averaging.psum_average(xk, mask_all[widx], axis_names, on_empty=on_empty)

    fn = jax.shard_map(worker, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    return fn(key, A, b, straggler_mask)


def distributed_sketch_solve_master(
    mesh: Mesh,
    spec: sk.SketchSpec,
    key: jax.Array,
    A: jax.Array,
    b: jax.Array,
    *,
    axis_names: tuple = ("data",),
    reg: float = 0.0,
    method: str = "fused",
    straggler_mask: Optional[jax.Array] = None,
    round_id: int = 0,
    on_empty: str = "nan",
):
    """Algorithm 1 in *master-sketch* mode (the paper's privacy deployment: only the
    master touches raw rows; workers see only sketch products).

    ``method="fused"`` (default): the master streams all q fused Grams
    ``(G_k, c_k)`` in one mesh-parallel batched pass over [A | b]
    (``operators.gram_batched`` — S_kA never materialized), ships O(d²) per worker
    instead of O(m·d), and each worker's solve is a d×d Cholesky. When
    ``spec.use_kernel`` is set and no real mesh shards the keys, that batched pass
    is ONE multi-worker Pallas launch (``SketchOp.gram_batched_kernel``) reading A
    once for all q sketches, rather than q kernel launches. Any other
    ``method`` keeps the two-pass reference: batch-materialize (S_kA, S_kb) via
    ``operators.sketch_data_batched`` and factorize per worker. Worker keys match
    :func:`distributed_sketch_solve`, so the two modes return the same x̄ for the
    same inputs (up to the solver's float tolerance).
    """
    q = operators.mesh_world(mesh, axis_names)
    straggler_mask = _checked_mask(straggler_mask, q)

    keys = prng.worker_keys(key, q, round_id)

    if method == "fused":
        Gs, cs = operators.gram_batched(
            spec, keys, A, b, mesh=mesh, axis_names=axis_names
        )  # (q, d, d), (q, d[, k])

        def worker_fused(G_blk, c_blk, mask_all):
            widx = _worker_index(axis_names)
            xk = solve.lstsq_gram(G_blk[0], c_blk[0], reg=reg)
            return averaging.psum_average(
                xk, mask_all[widx], axis_names, on_empty=on_empty
            )

        fn = jax.shard_map(
            worker_fused,
            mesh=mesh,
            in_specs=(P(axis_names), P(axis_names), P()),
            out_specs=P(),
            check_vma=False,
        )
        return fn(Gs, cs, straggler_mask)

    SA, Sb = operators.sketch_data_batched(
        spec, keys, A, b, mesh=mesh, axis_names=axis_names
    )  # (q, m, d), (q, m[, k])

    def worker(SA_blk, Sb_blk, mask_all):
        widx = _worker_index(axis_names)
        xk = solve.lstsq(SA_blk[0], Sb_blk[0], reg=reg, method=method)
        return averaging.psum_average(xk, mask_all[widx], axis_names, on_empty=on_empty)

    fn = jax.shard_map(
        worker,
        mesh=mesh,
        in_specs=(P(axis_names), P(axis_names), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(SA, Sb, straggler_mask)


def distributed_sketch_least_norm(
    mesh: Mesh,
    spec: sk.SketchSpec,
    key: jax.Array,
    A: jax.Array,
    b: jax.Array,
    *,
    axis_names: tuple = ("data",),
    straggler_mask: Optional[jax.Array] = None,
    round_id: int = 0,
    on_empty: str = "nan",
):
    """§V right-sketch averaging over the mesh (n < d). A replicated."""
    q = operators.mesh_world(mesh, axis_names)
    straggler_mask = _checked_mask(straggler_mask, q)

    def worker(key, A_rep, b_rep, mask_all):
        widx = _worker_index(axis_names)
        wkey = prng.worker_key(key, widx, round_id)
        xk = solve.sketch_least_norm(spec, wkey, A_rep, b_rep)
        return averaging.psum_average(xk, mask_all[widx], axis_names, on_empty=on_empty)

    fn = jax.shard_map(worker, mesh=mesh, in_specs=(P(), P(), P(), P()), out_specs=P(), check_vma=False)
    return fn(key, A, b, straggler_mask)


def _multiround_fn(mesh, spec, axis_names, reg, method, on_empty):
    """The per-round mesh program with ``round_id`` as a *traced* argument, jitted
    once — successive rounds are executions, not retraces."""

    def worker(key, A_rep, b_rep, mask_all, round_arr):
        global MULTIROUND_TRACE_COUNT
        MULTIROUND_TRACE_COUNT += 1  # Python side effect: fires once per trace
        widx = _worker_index(axis_names)
        wkey = prng.worker_key(key, widx, round_arr)
        xk = solve.sketch_and_solve(spec, wkey, A_rep, b_rep, reg=reg, method=method)
        return averaging.psum_average(xk, mask_all[widx], axis_names, on_empty=on_empty)

    fn = jax.shard_map(
        worker, mesh=mesh, in_specs=(P(), P(), P(), P(), P()), out_specs=P(), check_vma=False
    )
    return jax.jit(fn)


def distributed_sketch_solve_multiround(
    mesh: Mesh,
    spec: sk.SketchSpec,
    key: jax.Array,
    A: jax.Array,
    b: jax.Array,
    *,
    rounds: int,
    axis_names: tuple = ("data",),
    reg: float = 0.0,
    method: str = "fused",
    on_empty: str = "nan",
    latency=None,
    runtime_config=None,
    error_fn=None,
):
    """Elastic scaling in time: run Algorithm 1 for ``rounds`` successive waves of
    workers and average everything (effective q = rounds × mesh workers). Each wave
    reuses the same devices but fresh i.i.d. sketches — exactly how the serverless
    deployment keeps invoking new lambdas until the error target is met.

    Each round folds its id into the worker keys, so round r is a fresh i.i.d. batch.
    The round id is a *traced* scalar of one jitted mesh program, so the loop
    executes ``rounds`` times but traces once (``MULTIROUND_TRACE_COUNT`` audits
    this).

    Asynchronous mode: pass a :class:`repro.runtime.LatencyModel` as ``latency``
    (optionally a :class:`repro.runtime.RuntimeConfig` and an ``error_fn`` —
    ``"theory"`` / ``"probe"`` / callable) and the call becomes a thin wrapper over
    :func:`repro.runtime.serverless_sketch_solve`: the same (worker, round) key
    grid, but arrival-ordered streaming averaging, deadlines, retries, and early
    stopping instead of the synchronous wave barrier. Returns x̄ either way.
    """
    q = operators.mesh_world(mesh, axis_names)
    if latency is not None:
        from repro import runtime as rt

        res = rt.serverless_sketch_solve(
            spec, key, A, b, q=q, rounds=rounds, latency=latency,
            config=runtime_config, reg=reg, method=method, error_fn=error_fn,
        )
        return jnp.asarray(res.xbar, dtype=A.dtype)

    fn = _multiround_fn(mesh, spec, axis_names, reg, method, on_empty)
    mask = jnp.ones((q,), jnp.float32)
    acc = None
    for r in range(rounds):
        xbar_r = fn(key, A, b, mask, jnp.int32(r))
        acc = xbar_r if acc is None else acc + (xbar_r - acc) / (r + 1.0)
    return acc
