"""The benchmark's copies of the generators still give ``repro.data``'s output bitwise.

They were copied from ``repro.data.regression`` as it stood when the benchmark was
defined; if this test fails, the program's generators moved and the copies did not.
"""
import jax
import numpy as np

from bench import data
from repro.data import regression


def test_student_t_copy_is_bitwise():
    key = jax.random.PRNGKey(3)
    A, b = data.student_t(key, 300, 12, df=1.5, noise=0.1)
    A0, b0, _ = regression.student_t_regression(key, 300, 12, df=1.5, noise=0.1)
    np.testing.assert_array_equal(np.asarray(A), np.asarray(A0))
    np.testing.assert_array_equal(np.asarray(b), np.asarray(b0))


def test_emnist_like_copy_is_bitwise():
    key = jax.random.PRNGKey(4)
    A, B = data.emnist_like(key, 200, classes=47, img_dim=784, noise=1.0)
    A0, B0, _ = regression.emnist_like(key, 200, classes=47, img_dim=784, noise=1.0)
    np.testing.assert_array_equal(np.asarray(A), np.asarray(A0))
    np.testing.assert_array_equal(np.asarray(B), np.asarray(B0))


def test_make_reads_the_configuration():
    cfg = {"data": "student_t", "d": 12, "df": 1.5, "noise": 0.1}
    A, b = data.make(cfg, jax.random.PRNGKey(3), 300)
    assert A.shape == (300, 12) and b.shape == (300,)
