"""Master-side model averaging (Algorithm 1) with straggler resilience.

The paper's key systems claim is that because workers are i.i.d., the master may
average *whatever subset has arrived* — the estimator is unchanged with the realized
worker count q' ≤ q (Lemma 2 applies verbatim with q'). We express that as a masked
mean so the same code runs: (a) locally over a stacked (q, d) array, (b) inside
shard_map with ``jax.lax.psum`` over the worker mesh axis, (c) incrementally as a
streaming average when outputs trickle in (the serverless mode).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp



def worker_index(axis_names) -> jax.Array:
    """Linear worker index across (possibly multiple) mesh axes, inside shard_map.

    The one definition shared by the solver, gradient-compression, and sketch-DP
    paths — their worker keys must agree, so their index arithmetic must too.
    """
    idx = jnp.int32(0)
    for name in axis_names:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return idx


def _guard_empty(avg: jax.Array, den: jax.Array, on_empty: str) -> jax.Array:
    """Define x̄ when *no* worker made the deadline (den == 0).

    ``"nan"`` (default): NaN-poison the average — an all-straggler round has no
    estimator (Algorithm 1's q′ = 0), and silently returning 0 used to masquerade
    as a perfectly converged solution downstream. ``"zero"`` restores the legacy
    x̄ = 0 for callers that treat an empty round as a no-op contribution.
    """
    if on_empty == "zero":
        return avg
    if on_empty == "nan":
        return jnp.where(den > 0, avg, jnp.nan)
    raise ValueError(f"on_empty must be 'nan' or 'zero', got {on_empty!r}")


def masked_average(
    xs: jax.Array, mask: Optional[jax.Array] = None, *, on_empty: str = "nan"
) -> jax.Array:
    """Mean over axis 0 of xs (q, ...), counting only mask==1 rows.

    With mask=None this is the plain Algorithm-1 average. xs may have any rank
    (multi-output solutions stack as (q, d, k)): the mask broadcasts on axis 0.
    An all-zero mask yields NaN by default (``on_empty`` — see :func:`_guard_empty`).
    """
    if mask is None:
        return jnp.mean(xs, axis=0)
    m = mask.astype(xs.dtype).reshape((xs.shape[0],) + (1,) * (xs.ndim - 1))
    den = jnp.sum(mask.astype(xs.dtype))
    avg = jnp.sum(xs * m, axis=0) / jnp.maximum(den, 1.0)
    return _guard_empty(avg, den, on_empty)


def psum_average(
    x_local: jax.Array, mask_local: jax.Array, axis_name, *, on_empty: str = "nan"
) -> jax.Array:
    """Straggler-resilient average across one or more mesh axes (inside shard_map).

    Workers that missed the deadline pass mask_local=0; their x_local is ignored and
    the denominator is the realized worker count. When *every* worker missed, the
    result follows ``on_empty`` (NaN-poison by default — see :func:`_guard_empty`;
    eager drivers in ``core.distributed`` raise before tracing instead).
    """
    num = jax.lax.psum(x_local * mask_local, axis_name)
    den = jax.lax.psum(mask_local, axis_name)
    avg = num / jnp.maximum(den, 1.0)
    return _guard_empty(avg, den, on_empty)


@dataclasses.dataclass
class StreamingAverage:
    """Incremental master: absorb worker outputs as they arrive (serverless mode).

    Tracks the running mean and count; ``state`` is a pytree so it can live on-device.
    """

    mean: jax.Array
    count: jax.Array

    @classmethod
    def init(cls, d: int, dtype=jnp.float32) -> "StreamingAverage":
        return cls(mean=jnp.zeros((d,), dtype), count=jnp.zeros((), dtype))

    def update(self, x: jax.Array) -> "StreamingAverage":
        c = self.count + 1.0
        return StreamingAverage(mean=self.mean + (x - self.mean) / c, count=c)


jax.tree_util.register_pytree_node(
    StreamingAverage,
    lambda s: ((s.mean, s.count), None),
    lambda _, c: StreamingAverage(*c),
)


def simulate_straggler_mask(
    key: jax.Array, q: int, *, drop_prob: float = 0.0, deadline_quantile: float = 1.0
) -> jax.Array:
    """Simulate which of q workers made the deadline.

    drop_prob models hard failures (lambda never returns); deadline_quantile models a
    latency cutoff: worker runtimes ~ LogNormal and only the fastest fraction count.
    Returns a float mask (q,) with 1.0 = arrived.
    """
    kd, kt = jax.random.split(key)
    alive = jax.random.bernoulli(kd, 1.0 - drop_prob, (q,))
    if deadline_quantile >= 1.0:
        return alive.astype(jnp.float32)
    t = jax.random.lognormal(kt, shape=(q,))
    cutoff = jnp.quantile(t, deadline_quantile)
    return (alive & (t <= cutoff)).astype(jnp.float32)
