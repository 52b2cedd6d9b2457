"""Sketch-DP: the paper's operators applied to data-parallel training comms.

Three paper mechanisms become one shard_map'd gradient exchange:
  1. **Sketched compression** (Eq. privacy/bandwidth operator): each DP worker
     projects its gradient with a shared S (E[SᵀS]=I → unbiased), the psum runs in
     sketch space (m ≪ D floats over the wire), the result is back-projected.
  2. **Straggler masking** (Algorithm 1's partial averaging): workers that missed the
     step deadline contribute 0 and the denominator is the realized worker count —
     the paper's central claim that i.i.d. contributions can be averaged over
     whatever subset arrived, applied to gradients instead of solutions.
  3. **Deterministic worker keys**: the sketch S is derived from (base key, step) so
     every worker builds the same S with zero coordination (``prng.worker_key``).

This path targets pure DP (params replicated across the dp axis); the 40-cell
production configs use the GSPMD step (train/step.py) where TP/FSDP sharding makes
whole-gradient sketching inapplicable (documented in DESIGN.md §Beyond-paper).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


from repro.configs.base import ArchConfig
from repro.core import averaging, gradcomp
from repro.models import lm
from repro.optim import AdamWConfig, adamw_update
from repro.utils import tree as tu

PyTree = Any


def masked_compressed_mean(
    cfg: gradcomp.GradCompressionConfig,
    key: jax.Array,
    grads: PyTree,
    mask_local: jax.Array,
    axis_names,
) -> PyTree:
    """Straggler-resilient mean of gradients across ``axis_names`` (inside shard_map).

    Compression and masking compose because the sketch is linear:
        unsketch( psum(mask·S g) / psum(mask) ) = unsketch( S · masked-mean g ).
    """
    den = jnp.maximum(jax.lax.psum(mask_local, axis_names), 1.0)
    if not cfg.enabled:
        return jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g * mask_local, axis_names) / den, grads
        )
    payload, ctx = gradcomp.compress(cfg, key, grads)
    payload = jax.lax.psum(payload * mask_local, axis_names) / den
    return gradcomp.decompress(cfg, payload, ctx)


def make_sketch_dp_step(
    cfg: ArchConfig,
    opt_cfg: AdamWConfig,
    mesh: Mesh,
    *,
    comp: Optional[gradcomp.GradCompressionConfig] = None,
    axis_names: Tuple[str, ...] = ("data",),
    schedule: Optional[Callable] = None,
    remat: str = "none",
) -> Callable:
    """Returns ``step(state, batch, key, mask) -> (state, metrics)``.

    ``mask``: (q,) float — 1.0 for workers whose gradient made the deadline (the
    trainer's straggler simulator or a real deadline monitor supplies it).
    """
    comp = comp or gradcomp.GradCompressionConfig(enabled=False)

    def local_grads(params, local_batch, key, mask_all):
        mask = mask_all[averaging.worker_index(axis_names)]

        def loss_fn(p):
            loss, aux = lm.lm_loss(p, cfg, local_batch, rules=None, plan=lm.ExecPlan(remat=remat))
            return loss, aux

        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        mean_grads = masked_compressed_mean(comp, key, grads, mask, axis_names)
        den = jnp.maximum(jax.lax.psum(mask, axis_names), 1.0)
        mean_loss = jax.lax.psum(loss * mask, axis_names) / den
        return mean_grads, mean_loss

    batch_spec = {"tokens": P(axis_names), "labels": P(axis_names), "loss_mask": P(axis_names)}
    smap = jax.shard_map(
        local_grads,
        mesh=mesh,
        in_specs=(P(), batch_spec, P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )

    @jax.jit
    def step(state, batch, key, mask):
        grads, loss = smap(state["params"], batch, key, mask)
        lr_scale = schedule(state["step"]) if schedule is not None else 1.0
        new_params, new_opt, om = adamw_update(
            opt_cfg, state["params"], grads, state["opt"], lr_scale=lr_scale
        )
        new_state = {"params": new_params, "opt": new_opt, "step": state["step"] + 1}
        return new_state, {"loss": loss, **om}

    return step
