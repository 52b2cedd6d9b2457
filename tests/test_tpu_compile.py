"""The fused gram kernels compile for a TPU v5e at the chip smoke's shapes.

Interpret mode (every other kernel test) cannot see what Mosaic refuses: casts it
has no lowering for, reshapes it cannot lay out, VMEM it does not have. These
tests compile each family's single- and multi-worker gram op for one described
v5e chip (no chip attached), with ``interpret=False``, at d+1=1001 (padded to
1024), m=10000, q=8 and n=2^19 — the shapes ``chip_smoke.py`` runs — and check that
the compiled program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described only inside the module fixture: one process at a time
may load the TPU library, so nothing here touches it at import or collection.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.fwht import ops as fops
from repro.kernels.gaussian import ops as gops
from repro.kernels.rademacher import ops as rops
from repro.kernels.sjlt import ops as sops

N, D, M, Q, S = 2**19, 1001, 10_000, 8, 4


@pytest.fixture(scope="module")
def one_chip():
    """A SingleDeviceSharding on one described v5e chip; skips where none can be described."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the persistent cache.
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    if saved_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _args(sharding, kind: str, q):
    """Shape-only arguments for one family: (data, *family operands); q=None is single."""
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)  # noqa: E731
    lead = () if q is None else (q,)
    A = sds((N, D))
    if kind in ("gaussian", "rademacher"):
        keys = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), q or 2))
        key = sds(keys.shape[1:] if q is None else keys.shape, keys.dtype)
        return A, key
    if kind == "sjlt":
        return A, sds(lead + (N, S), jnp.int32), sds(lead + (N, S))
    return A, sds(lead + (M,), jnp.int32), sds(lead + (2,), jnp.uint32)


OPS = {
    ("gaussian", False): lambda A, k: gops.gaussian_gram(k, A, M, interpret=False),
    ("gaussian", True): lambda A, k: gops.gaussian_gram_multi(k, A, M, interpret=False),
    ("rademacher", False): lambda A, k: rops.rademacher_gram(k, A, M, interpret=False),
    ("rademacher", True): lambda A, k: rops.rademacher_gram_multi(k, A, M, interpret=False),
    ("sjlt", False): lambda A, bk, sg: sops.sjlt_gram(A, bk, sg, M, interpret=False),
    ("sjlt", True): lambda A, bk, sg: sops.sjlt_gram_multi(A, bk, sg, M, interpret=False),
    ("srht", False): lambda A, r, kw: fops.srht_gram(A, r, kw, interpret=False),
    ("srht", True): lambda A, r, kw: fops.srht_gram_multi(A, r, kw, interpret=False),
}


@pytest.mark.parametrize("multi", [False, True], ids=["single", "multi"])
@pytest.mark.parametrize("kind", ["gaussian", "rademacher", "sjlt", "srht"])
def test_gram_kernel_compiles_for_v5e(one_chip, kind, multi):
    text = _compiled_text(OPS[kind, multi], *_args(one_chip, kind, Q if multi else None))
    assert "tpu_custom_call" in text, f"{kind} ({'multi' if multi else 'single'}): no Mosaic kernel"
