"""Every mesh in the repo is built here (:func:`make_mesh`), plus the production shapes.

Functions (not module constants) so importing this file never touches jax device
state — the dry-run must set XLA_FLAGS before the first device query.

Mesh shapes (TPU v5e target):
  * single pod : (16, 16)    axes ("data", "model")   = 256 chips
  * multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips

Axis roles: the batch shards over ("pod", "data") — pure DP across pods keeps the
only cross-pod (DCN) collective the gradient reduce; "model" carries Megatron TP
within a pod's ICI domain. FSDP (ZeRO-3 parameter sharding) rides the "data" axis.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

from repro.distributed.sharding import ShardingRules


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """The one mesh constructor: ``jax.make_mesh`` with every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which a ``shard_map`` or
    ``jit`` over the mesh must run inside ``jax.set_mesh``. The solvers here take
    the mesh as an argument instead, which ``Auto`` axes allow.
    """
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def production_rules(*, multi_pod: bool = False) -> ShardingRules:
    dp = ("pod", "data") if multi_pod else ("data",)
    return ShardingRules(dp=dp, fsdp="data", tensor="model")


def make_smoke_mesh(n_devices: int = 0) -> Mesh:
    """A tiny mesh over whatever devices exist (tests; 1 device -> (1,1))."""
    n = n_devices or len(jax.devices())
    model = 1
    for cand in (4, 2, 1):
        if n % cand == 0 and cand <= n:
            model = cand
            break
    return make_mesh((n // model, model), ("data", "model"))
