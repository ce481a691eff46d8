"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax call).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding rules of this repo
    (``runtime/sharding.py``) are annotations the compiler propagates, not
    explicit-sharding types (newer JAX defaults every axis to Explicit)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (elastic re-mesh / tests)."""
    return _mesh(shape, axes)


def largest_model_axis(n: int, cap=None) -> int:
    """Largest divisor of ``n`` not exceeding ``cap`` (default ``n``) — the
    biggest tensor-parallel axis a ``(data, model)`` factorization of ``n``
    local devices supports."""
    cap = n if cap is None else max(1, min(int(cap), n))
    for m in range(cap, 0, -1):
        if n % m == 0:
            return m
    return 1


def make_host_mesh(*, model=None, max_model=None):
    """Whatever devices exist locally, as an examples/tests mesh.

    Default: the historical 1-D ``("data",)`` mesh.  ``model`` asks for a
    2-D ``(data, model)`` factorization instead — an int names the model
    (TP) axis size exactly (must divide the local device count), ``"max"``
    picks the largest divisor (optionally capped by ``max_model``).  Eight
    host CPU devices (``--xla_force_host_platform_device_count=8``) then
    give e.g. ``model=4`` -> a (2, 4) ``(data, model)`` mesh for exercising
    sharded compressed serving without an accelerator.
    """
    n = len(jax.devices())
    if model is None and max_model is None:
        return _mesh((n,), ("data",))
    if model in (None, "max"):
        model = largest_model_axis(n, max_model)
    model = int(model)
    if model < 1 or n % model:
        raise ValueError(
            f"model axis {model} does not divide the {n} local devices")
    return _mesh((n // model, model), ("data", "model"))
