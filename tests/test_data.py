"""Data pipeline: determinism, shard/row disjointness, learnability structure."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.data import regression
from repro.data import (
    airline_like,
    emnist_like,
    gaussian_regression,
    lm_batch,
    lm_eval_batch,
    student_t_regression,
)


def test_lm_batch_deterministic_and_step_dependent():
    a = lm_batch(0, 3, batch=4, seq=32, vocab=97)
    b = lm_batch(0, 3, batch=4, seq=32, vocab=97)
    np.testing.assert_array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))
    c = lm_batch(0, 4, batch=4, seq=32, vocab=97)
    assert not np.array_equal(np.asarray(a["tokens"]), np.asarray(c["tokens"]))
    assert int(a["tokens"].max()) < 97 and int(a["tokens"].min()) >= 0


def test_lm_batch_row_offset_shards_disjoint():
    """Two shards of the same global batch must produce different rows, and
    regenerating a shard (worker replacement) must be bitwise identical."""
    s0 = lm_batch(0, 1, batch=2, seq=16, vocab=97, row_offset=0)
    s1 = lm_batch(0, 1, batch=2, seq=16, vocab=97, row_offset=2)
    full = lm_batch(0, 1, batch=4, seq=16, vocab=97)
    np.testing.assert_array_equal(np.asarray(full["tokens"][:2]), np.asarray(s0["tokens"]))
    np.testing.assert_array_equal(np.asarray(full["tokens"][2:]), np.asarray(s1["tokens"]))


def test_eval_split_disjoint():
    tr = lm_batch(0, 0, batch=4, seq=16, vocab=97)
    ev = lm_eval_batch(0, 0, batch=4, seq=16, vocab=97)
    assert not np.array_equal(np.asarray(tr["tokens"]), np.asarray(ev["tokens"]))


def test_lm_batch_has_learnable_bigram_structure():
    b = lm_batch(0, 0, batch=16, seq=128, vocab=53, p_pattern=0.9)
    toks = np.asarray(b["tokens"])
    a, c = 31337 % 53, 7919 % 53
    pred = (a * toks[:, :-1] + c) % 53
    frac = (pred == toks[:, 1:]).mean()
    assert frac > 0.8, frac  # ~p_pattern of transitions follow the affine map


def test_regression_generators():
    A, b, meta = gaussian_regression(jax.random.PRNGKey(0), 128, 8)
    assert A.shape == (128, 8) and b.shape == (128,)
    A, b, meta = student_t_regression(jax.random.PRNGKey(0), 128, 8, df=1.5)
    assert np.isfinite(np.asarray(A)).all()
    A, b, meta = airline_like(jax.random.PRNGKey(0), 256)
    assert A.shape == (256, meta["d"])
    assert set(np.unique(np.asarray(b))) <= {0.0, 1.0}
    A, B, meta = emnist_like(jax.random.PRNGKey(0), 64, classes=5, img_dim=16)
    assert B.shape == (64, 5)
    np.testing.assert_allclose(np.asarray(B.sum(axis=1)), 1.0)


def test_student_t_row_blocks_match_shape_and_tail(monkeypatch):
    """Row-blocked draws (how large n fits a device) keep shape, clip and planting."""
    monkeypatch.setattr(regression, "T_BLOCK_ROWS", 256)  # 4 blocks, the last one cut
    A, b, meta = student_t_regression(jax.random.PRNGKey(0), 1000, 8, df=1.5)
    assert A.shape == (1000, 8) and b.shape == (1000,)
    assert float(jnp.max(jnp.abs(A))) <= 1e3
    assert not np.array_equal(np.asarray(A[:256]), np.asarray(A[256:512]))  # fresh key per block
    resid = b - A @ meta["x_truth"]
    assert float(jnp.std(resid)) < 0.2
    A2, _, _ = student_t_regression(jax.random.PRNGKey(0), 1000, 8, df=1.5)
    np.testing.assert_array_equal(np.asarray(A), np.asarray(A2))
