"""Master-side averaging + straggler machinery."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import averaging


def test_masked_average_plain_mean():
    xs = jax.random.normal(jax.random.PRNGKey(0), (8, 5))
    np.testing.assert_allclose(
        np.asarray(averaging.masked_average(xs)), np.asarray(jnp.mean(xs, 0)), rtol=1e-6
    )


def test_masked_average_subset():
    xs = jnp.stack([jnp.full((3,), float(i)) for i in range(4)])
    mask = jnp.array([1.0, 0.0, 0.0, 1.0])
    np.testing.assert_allclose(np.asarray(averaging.masked_average(xs, mask)), [1.5] * 3)


def test_masked_average_all_stragglers_poisons():
    """q' = 0 has no estimator: NaN by default, legacy x̄=0 only by explicit opt-in."""
    xs = jnp.ones((4, 3))
    out = averaging.masked_average(xs, jnp.zeros((4,)))
    assert np.isnan(np.asarray(out)).all()
    out0 = averaging.masked_average(xs, jnp.zeros((4,)), on_empty="zero")
    np.testing.assert_array_equal(np.asarray(out0), 0.0)
    # non-empty masks are untouched by the guard
    out1 = averaging.masked_average(xs, jnp.array([0.0, 1.0, 0.0, 0.0]))
    np.testing.assert_allclose(np.asarray(out1), 1.0)


def test_streaming_average_matches_batch():
    xs = jax.random.normal(jax.random.PRNGKey(0), (10, 4))
    st = averaging.StreamingAverage.init(4)
    for i in range(10):
        st = st.update(xs[i])
    np.testing.assert_allclose(
        np.asarray(st.mean), np.asarray(jnp.mean(xs, 0)), rtol=1e-5, atol=1e-7
    )
    assert int(st.count) == 10


def test_streaming_average_is_pytree():
    st = averaging.StreamingAverage.init(4)
    leaves = jax.tree_util.tree_leaves(st)
    assert len(leaves) == 2
    st2 = jax.jit(lambda s, x: s.update(x))(st, jnp.ones((4,)))
    assert float(st2.count) == 1.0


def test_straggler_mask_statistics():
    q = 1000
    mask = averaging.simulate_straggler_mask(jax.random.PRNGKey(0), q, drop_prob=0.2)
    frac = float(mask.mean())
    assert 0.7 < frac < 0.9
    mask2 = averaging.simulate_straggler_mask(
        jax.random.PRNGKey(1), q, drop_prob=0.0, deadline_quantile=0.5
    )
    assert abs(float(mask2.mean()) - 0.5) < 0.1


def test_psum_average_single_device_mesh():
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    f = jax.shard_map(
        lambda x, m: averaging.psum_average(x, m, "data"),
        mesh=mesh,
        in_specs=(P("data"), P("data")),
        out_specs=P("data"),
    )
    x = jnp.ones((1, 3))
    out = f(x, jnp.ones((1,)))
    np.testing.assert_allclose(np.asarray(out), np.ones((1, 3)))
