"""Validated ``REPRO_*`` environment parsing — the one sanctioned env-read surface.

Every knob the library reads from the environment goes through this module. Two
reasons this is a hard rule (machine-checked by the ``env-read-in-trace``
reprolint rule, which flags ``os.environ`` / ``os.getenv`` anywhere else under
``repro/``):

  * **Trace capture.** Several knobs (``REPRO_RNG_ROUNDS``,
    ``REPRO_PALLAS_INTERPRET``) are resolved at *trace* time: the value is baked
    into the jit cache of whatever traces first. An ad-hoc read buried inside
    traced code makes that capture invisible; routing every read through here
    keeps the surface auditable and the resolution points documented.
  * **Validation.** A typo'd value must fail loudly, naming the variable — not
    silently fall back or raise a bare ``ValueError: invalid literal`` from
    somewhere deep in a trace.

This module is intentionally stdlib-only (no jax/numpy imports): it sits below
``repro.kernels.common`` in the import graph.
"""
from __future__ import annotations

import os
import pathlib

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


def read_raw(name: str, default: str = "") -> str:
    """The stripped raw value of ``name`` (``default`` when unset)."""
    return os.environ.get(name, default).strip()


def read_bool(name: str, default: bool | None = None) -> bool | None:
    """Tri-state boolean: True/False when set, ``default`` when unset or empty.

    Accepts ``1/true/yes/on`` and ``0/false/no/off`` (case-insensitive); anything
    else raises a ``ValueError`` naming the variable.
    """
    raw = read_raw(name).lower()
    if not raw:
        return default
    if raw in _TRUE:
        return True
    if raw in _FALSE:
        return False
    raise ValueError(
        f"{name} must be a boolean flag ({'/'.join(_TRUE)} or {'/'.join(_FALSE)}), got {raw!r}"
    )


def read_int(
    name: str,
    default: int | None = None,
    *,
    positive: bool = False,
    multiple_of: int | None = None,
) -> int | None:
    """Integer knob: parsed value when set, ``default`` when unset or empty.

    A non-integer value, a non-positive value under ``positive=True``, or a value
    that is not a multiple of ``multiple_of`` all raise a ``ValueError`` naming
    the variable and the constraint.
    """
    raw = read_raw(name)
    if not raw:
        return default
    try:
        value = int(raw)
    except ValueError:
        raise ValueError(f"{name} must be an integer, got {raw!r}") from None
    constraint = None
    if positive and multiple_of is not None:
        constraint = f"a positive multiple of {multiple_of}"
        bad = value <= 0 or value % multiple_of
    elif positive:
        constraint = "a positive integer"
        bad = value <= 0
    elif multiple_of is not None:
        constraint = f"a multiple of {multiple_of}"
        bad = bool(value % multiple_of)
    else:
        bad = False
    if bad:
        raise ValueError(f"{name} must be {constraint}, got {value}")
    return value


CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/utils/env.py -> the checkout root
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``<checkout>/.jax_cache`` (gitignored). Never a
    temporary or per-process path: the directory is part of the cache key."""
    return read_raw(CACHE_ENV) or str(CHECKOUT / ".jax_cache")


def configure_compile_cache() -> str:
    """Turn the persistent compilation cache on at :func:`compile_cache_dir`.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and nothing
    else is set here. Call once, before the first compile, from entry points
    (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``). Returns the path.
    """
    path = compile_cache_dir()
    if not read_raw(CACHE_ENV):
        import jax  # deferred: this module stays stdlib-only at import

        jax.config.update("jax_compilation_cache_dir", path)
    return path
