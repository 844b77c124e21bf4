"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is an
outer data-parallel axis whose gradient reduction crosses the inter-pod
DCI — kept to one (optionally int8-compressed) all-reduce per step.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the models place arrays with
    ``with_sharding_constraint``, which only accepts Auto mesh axes
    (``make_mesh`` defaults to Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0, \
        f"model={model} must divide the {n} visible devices"
    return make_auto_mesh((n // model, model), ("data", "model"))


# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12        # per chip
PEAK_FLOPS_INT8 = 394e12        # per chip (int8 MXU path)
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~3 links usable / chip)
HBM_PER_CHIP = 16 * 2 ** 30     # 16 GiB
