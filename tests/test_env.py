"""repro.utils.env — the one sanctioned, validated env-read surface."""
from __future__ import annotations

import pytest

from repro.utils import env


def test_read_raw_strips_and_defaults(monkeypatch):
    monkeypatch.setenv("REPRO_TEST_RAW", "  hello ")
    assert env.read_raw("REPRO_TEST_RAW") == "hello"
    monkeypatch.delenv("REPRO_TEST_RAW", raising=False)
    assert env.read_raw("REPRO_TEST_RAW", "fallback") == "fallback"


@pytest.mark.parametrize("raw,expected", [
    ("1", True), ("true", True), ("YES", True), ("on", True),
    ("0", False), ("False", False), ("no", False), ("OFF", False),
])
def test_read_bool_accepts_both_spellings(monkeypatch, raw, expected):
    monkeypatch.setenv("REPRO_TEST_FLAG", raw)
    assert env.read_bool("REPRO_TEST_FLAG") is expected


def test_read_bool_tristate_default(monkeypatch):
    monkeypatch.delenv("REPRO_TEST_FLAG", raising=False)
    assert env.read_bool("REPRO_TEST_FLAG") is None
    assert env.read_bool("REPRO_TEST_FLAG", True) is True
    monkeypatch.setenv("REPRO_TEST_FLAG", "")
    assert env.read_bool("REPRO_TEST_FLAG", False) is False


def test_read_bool_rejects_garbage_naming_the_variable(monkeypatch):
    monkeypatch.setenv("REPRO_TEST_FLAG", "maybe")
    with pytest.raises(ValueError, match="REPRO_TEST_FLAG must be a boolean flag"):
        env.read_bool("REPRO_TEST_FLAG")


def test_read_int_parses_and_defaults(monkeypatch):
    monkeypatch.setenv("REPRO_TEST_N", "24")
    assert env.read_int("REPRO_TEST_N") == 24
    monkeypatch.delenv("REPRO_TEST_N", raising=False)
    assert env.read_int("REPRO_TEST_N", 8) == 8


@pytest.mark.parametrize("raw,fragment", [
    ("x", "must be an integer, got 'x'"),
    ("0", "must be a positive multiple of 4, got 0"),
    ("-4", "must be a positive multiple of 4, got -4"),
    ("6", "must be a positive multiple of 4, got 6"),
])
def test_read_int_constraint_errors_name_variable(monkeypatch, raw, fragment):
    monkeypatch.setenv("REPRO_TEST_N", raw)
    with pytest.raises(ValueError) as e:
        env.read_int("REPRO_TEST_N", positive=True, multiple_of=4)
    assert "REPRO_TEST_N" in str(e.value) and fragment in str(e.value)


def test_read_int_positive_only(monkeypatch):
    monkeypatch.setenv("REPRO_TEST_N", "-1")
    with pytest.raises(ValueError, match="must be a positive integer"):
        env.read_int("REPRO_TEST_N", positive=True)


def test_kernel_knobs_route_through_env_surface(monkeypatch):
    """The real consumers (kernels.common) honor the validated surface."""
    from repro.kernels import common

    monkeypatch.setenv("REPRO_RNG_ROUNDS", "12")
    assert common.rng_rounds() == 12
    monkeypatch.setenv("REPRO_RNG_ROUNDS", "6")
    with pytest.raises(ValueError, match="REPRO_RNG_ROUNDS"):
        common.rng_rounds()
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert common.default_interpret() is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert common.default_interpret() is False


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    updates = []
    import jax

    monkeypatch.setattr(jax.config, "update", lambda name, value: updates.append((name, value)))
    path = env.configure_compile_cache()
    assert path == str(env.CHECKOUT / ".jax_cache")
    assert (env.CHECKOUT / "chip_smoke.py").exists()  # CHECKOUT is the repo root
    assert updates == [("jax_compilation_cache_dir", path)]


def test_compile_cache_env_dir_is_used_and_nothing_else_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    updates = []
    import jax

    monkeypatch.setattr(jax.config, "update", lambda name, value: updates.append((name, value)))
    assert env.configure_compile_cache() == str(tmp_path)
    assert updates == []
