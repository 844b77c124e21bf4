"""Mesh context + logical-axis resolution — the one sharding vocabulary.

Models speak LOGICAL axes ("dp" data-parallel, "tp" tensor-parallel,
"dp+tp" both combined, None replicated); this module maps them onto
whatever mesh is active:

  2-axis mesh ("data", "model")         dp -> "data",           tp -> "model"
  3-axis mesh ("pod", "data", "model")  dp -> ("pod", "data"),  tp -> "model"

``constrain``/``constrain_heads`` are no-ops when no mesh is active, so
every model file can sprinkle sharding annotations and still run unchanged
on CPU tests and single-host launches.

The active mesh is resolved from (in order):
  1. the explicit :func:`use_mesh` context stack (nestable, thread-local);
  2. jax's own mesh context, ``with jax.set_mesh(mesh):`` (what
     launch/dryrun uses).

Divisibility fallback: a dimension whose size does not divide the product
of its mapped mesh axes is REPLICATED (per dimension, not per spec) —
oddball shapes degrade to replication instead of crashing the partitioner.
"""
from __future__ import annotations

import contextlib
import math
import threading
import warnings
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# Logical -> candidate mesh axes, in the order they combine.
_LOGICAL_AXES = {
    "dp": ("pod", "data"),
    "tp": ("model",),
}


# ---------------------------------------------------------------------------
# Mesh context stack
# ---------------------------------------------------------------------------

_local = threading.local()


def _stack() -> list:
    if not hasattr(_local, "meshes"):
        _local.meshes = []
    return _local.meshes


@contextlib.contextmanager
def use_mesh(mesh):
    """Push ``mesh`` as the active mesh for the enclosed block (nestable)."""
    _stack().append(mesh)
    try:
        yield mesh
    finally:
        _stack().pop()


def _jax_context_mesh():
    """The mesh of an enclosing ``with jax.set_mesh(mesh):`` block, if any.

    The abstract mesh (axis names and sizes) is what jax exposes both
    inside and outside a trace, and all :func:`constrain` needs."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def active_mesh():
    """Innermost active mesh, or None (=> all dist ops are no-ops)."""
    stack = _stack()
    if stack:
        return stack[-1]
    return _jax_context_mesh()


@contextlib.contextmanager
def manual_mode():
    """Mark the enclosed trace as running INSIDE a ``shard_map`` body.

    ``with_sharding_constraint`` on a mesh axis is illegal under manual
    (per-device) execution — the axis is already consumed by the shard
    map — so :func:`constrain`/:func:`constrain_heads` become identity
    while this flag is up.  Engines wrap their shard-mapped program
    bodies in it (thread-local, trace-time: the flag is read while the
    body traces, never at run time)."""
    prev = getattr(_local, "manual", False)
    _local.manual = True
    try:
        yield
    finally:
        _local.manual = prev


def in_manual_mode() -> bool:
    return getattr(_local, "manual", False)


def dp_size(mesh=None) -> int:
    """Total data-parallel ways of the active (or given) mesh."""
    mesh = mesh if mesh is not None else active_mesh()
    if mesh is None:
        return 1
    return math.prod(mesh.shape[a] for a in _LOGICAL_AXES["dp"]
                     if a in mesh.shape)


def tp_size(mesh=None) -> int:
    """Tensor-parallel ways (size of the "model" axis), 1 without a mesh."""
    mesh = mesh if mesh is not None else active_mesh()
    if mesh is None:
        return 1
    return math.prod(mesh.shape[a] for a in _LOGICAL_AXES["tp"]
                     if a in mesh.shape)


# ---------------------------------------------------------------------------
# Logical -> mesh resolution
# ---------------------------------------------------------------------------

def mesh_axes_for(mesh, logical: Optional[str]) -> Tuple[str, ...]:
    """Mesh axes a logical name maps to on this mesh ("dp+tp" combines)."""
    if logical is None:
        return ()
    names = set(mesh.axis_names)
    out = []
    for part in logical.split("+"):
        try:
            candidates = _LOGICAL_AXES[part]
        except KeyError:
            raise ValueError(f"unknown logical axis {part!r}; "
                             f"known: {sorted(_LOGICAL_AXES)}") from None
        out.extend(a for a in candidates if a in names)
    return tuple(out)


# divisibility fallbacks already warned about (one-shot per distinct
# (logical axis, mesh axes, dim, shape) — a serving loop resolves the
# same specs every tick and must not spam)
_warned_fallbacks: set = set()


def logical_to_mesh(mesh, logical_axes: Sequence[Optional[str]],
                    shape: Sequence[int]) -> P:
    """Resolve per-dimension logical axes into a PartitionSpec.

    Per-dimension divisibility fallback: if the dim size does not divide
    the product of the mapped mesh-axis sizes, that dimension replicates
    — with a one-shot RuntimeWarning naming the axis and shape, so a
    half-sharded placement is visible instead of discovered via
    benchmarks.  A mesh axis is consumed at most once per spec (first
    dim wins).
    """
    assert len(logical_axes) == len(shape), (logical_axes, shape)
    used: set = set()
    entries = []
    for dim, logical in zip(shape, logical_axes):
        axes = tuple(a for a in mesh_axes_for(mesh, logical)
                     if a not in used)
        size = math.prod(mesh.shape[a] for a in axes) if axes else 0
        if not axes or size <= 1 or dim % size != 0:
            if axes and size > 1 and dim > 1:
                # a real sharding request fell back (absent/trivial axes
                # and singleton dims lose nothing — stay silent there)
                key = (logical, axes, int(dim), tuple(shape))
                if key not in _warned_fallbacks:
                    _warned_fallbacks.add(key)
                    warnings.warn(
                        f"logical axis {logical!r} -> mesh axes "
                        f"{axes} (size {size}) does not divide dim "
                        f"{dim} of shape {tuple(shape)}; replicating "
                        f"this dimension", RuntimeWarning, stacklevel=2)
            entries.append(None)
            continue
        used.update(axes)
        entries.append(axes[0] if len(axes) == 1 else axes)
    return P(*entries)


# ---------------------------------------------------------------------------
# Sharding constraints (no-ops without a mesh)
# ---------------------------------------------------------------------------

def constrain(x, logical_axes: Sequence[Optional[str]]):
    """``with_sharding_constraint`` in logical axes; identity off-mesh
    and inside ``shard_map`` bodies (see :func:`manual_mode`)."""
    mesh = active_mesh()
    if mesh is None or in_manual_mode():
        return x
    spec = logical_to_mesh(mesh, logical_axes, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_heads(x, head_dim: int, alt_dim: int, use_head: bool):
    """Shard dim 0 over dp and ONE of (head_dim | alt_dim) over tp.

    Attention uses this to keep q/k/v/cache consistently sharded: when the
    (KV-)head count divides tp, shard heads (Megatron); otherwise fall
    back to sharding the per-head feature dim (``alt_dim``).
    """
    axes: list = [None] * x.ndim
    axes[0] = "dp"
    axes[head_dim if use_head else alt_dim] = "tp"
    return constrain(x, tuple(axes))
