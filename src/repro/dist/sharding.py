"""Path-based sharding rules for every parameter/optimizer/batch/cache leaf.

The scheme is Megatron + FSDP expressed in logical axes (resolved by
:mod:`repro.dist.api`):

  * column-parallel linears (wq/wk/wv, wg/wu/wi, in_proj, router, head):
    output dim over "tp", input dim over "dp" (FSDP);
  * row-parallel linears (wo, wd, out_proj): input dim over "tp", output
    dim over "dp";
  * MoE expert stacks (..., E, d_in, d_out): experts over "tp" (expert
    parallelism) AND d_in over "dp" — sharded on both mesh axes;
  * embeddings/head: padded vocab over "tp", d_model over "dp";
  * Mamba2 conv kernels: channel dim over "tp"; scalar SSM params
    (A_log, D, dt_bias) and all norms replicate;
  * hybrid LoRA adapters: ``a`` FSDP-sharded on d_in, ``b`` on d_out/tp.

Every rule degrades to replication through the per-dimension divisibility
fallback in :func:`repro.dist.api.logical_to_mesh`, so one rule set covers
all ten configs (and their smoke variants) on any mesh.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import api
from repro.dist.api import logical_to_mesh      # noqa: F401  (re-export)

# Linear dicts whose INPUT dim is tensor-parallel (the reduction dim of
# the second GEMM in each pair — output resharded by one all-reduce).
_ROW_PARALLEL = ("wo", "wd", "out_proj")
# Leaf names that always replicate (norm scales, biases, SSM scalars).
_REPLICATED = frozenset(("scale", "bias", "b", "conv_b", "A_log", "D",
                         "dt_bias", "kpos", "step"))
_EXPERT_STACK = ("wg", "wu", "wd")
_LINEAR_LEAVES = ("w", "q", "q4", "s")


def _keys(path) -> Tuple[str, ...]:
    return tuple(str(getattr(p, "key", p)) for p in path)


def _logical_spec(keys: Sequence[str], nd: int,
                  plan=None) -> Tuple[Optional[str], ...]:
    """Per-dimension logical axes for a parameter leaf at ``keys``.

    ``plan`` (a :class:`repro.dist.placement.PlacementPlan`) overrides
    the base Megatron/FSDP rule with replication for leaves whose priced
    entry the planner fully replicated — extra resident copies trade
    memory for amortized latency (LRMP-style).  Entries the plan left at
    one copy (or partially replicated — pspecs cannot express partial
    replica counts) keep the base rule.
    """
    if nd == 0:
        return ()
    if plan is not None and plan.replicates(keys):
        return (None,) * nd
    name = keys[-1]
    parent = keys[-2] if len(keys) >= 2 else ""
    if "lora" in keys and name in ("a", "b"):
        spec = [None] * nd
        spec[-2 if name == "a" else -1] = "dp" if name == "a" else "tp"
        return tuple(spec)
    if name in _REPLICATED or nd == 1:
        return (None,) * nd
    if name == "emb":
        return (None,) * (nd - 2) + ("tp", "dp")
    if "experts" in keys and (name in _EXPERT_STACK
                              or parent in _EXPERT_STACK):
        # (..., E, d_in, d_out) train form, or {"q", "s"} serve form whose
        # middle dim is 1 for scales (falls back to replication there).
        if nd >= 3:
            return (None,) * (nd - 3) + ("tp", "dp", None)
        return (None,) * nd
    if name == "conv_w":
        return (None,) * (nd - 1) + ("tp",)
    if name in _LINEAR_LEAVES and nd >= 2:
        if parent in _ROW_PARALLEL:
            return (None,) * (nd - 2) + ("tp", "dp")
        return (None,) * (nd - 2) + ("dp", "tp")
    return (None,) * nd


def param_pspec(path, leaf, plan=None) -> Tuple[Optional[str], ...]:
    """Logical per-dimension spec for one parameter leaf (len == ndim)."""
    return _logical_spec(_keys(path), leaf.ndim, plan=plan)


def param_shardings(params, mesh, plan=None):
    """NamedSharding pytree mirroring ``params`` (train or serve form).
    ``plan`` applies a placement planner's replication overrides."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, logical_to_mesh(mesh, param_pspec(path, leaf, plan),
                                  leaf.shape)),
        params)


# ---------------------------------------------------------------------------
# Optimizer state — moments mirror parameter sharding (FSDP shards Adam
# state too); the int8 / factored codecs reuse the base parameter's spec.
# ---------------------------------------------------------------------------

_CODEC_SUFFIXES = frozenset(("q", "s", "vr", "vc"))


def opt_pspec(path, leaf) -> Tuple[Optional[str], ...]:
    keys = _keys(path)
    if keys[0] == "step":
        return (None,) * leaf.ndim
    base = keys[1:]                       # drop the leading "m" / "v"
    name = base[-1] if base else ""
    if name in _CODEC_SUFFIXES:
        pkeys = base[:-1]
        if name in ("q", "s"):            # int8 codec: q = param shape,
            return _logical_spec(pkeys, leaf.ndim)   # s last dim 1 -> repl.
        full = _logical_spec(pkeys, leaf.ndim + 1)   # factored v drops a dim
        return full[:-1] if name == "vr" else full[:-2] + full[-1:]
    return _logical_spec(base, leaf.ndim)


def opt_shardings(opt, mesh):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, logical_to_mesh(mesh, opt_pspec(path, leaf), leaf.shape)),
        opt)


# ---------------------------------------------------------------------------
# Batches / activations
# ---------------------------------------------------------------------------

def batch_pspec(leaf) -> Tuple[Optional[str], ...]:
    """Inputs shard their leading (batch) dim over dp, rest replicated."""
    if leaf.ndim == 0:
        return ()
    return ("dp",) + (None,) * (leaf.ndim - 1)


def batch_shardings(batch, mesh):
    return jax.tree.map(
        lambda leaf: NamedSharding(
            mesh, logical_to_mesh(mesh, batch_pspec(leaf), leaf.shape)),
        batch)


def shard_batch(batch, mesh=None):
    """device_put a host batch onto the active mesh (identity off-mesh)."""
    mesh = mesh if mesh is not None else api.active_mesh()
    if mesh is None:
        return batch
    return jax.device_put(batch, batch_shardings(batch, mesh))


def bits_pspec(leaf) -> Tuple[Optional[str], ...]:
    """Per-layer bit tables: (L,) replicates; a per-request (B, L) bit
    matrix shards its batch dim over dp so each dp shard carries exactly
    the bit rows of the activation rows it owns."""
    if leaf.ndim == 2:
        return ("dp", None)
    return (None,) * leaf.ndim


def budgets_pspec(leaf) -> Tuple[Optional[str], ...]:
    """Per-request (B,) budget vectors — the serving runtime's batched
    admission state — shard over dp like the rows they gate, so the
    controller's select/gather lands its (B, L) bit matrix already
    dp-placed instead of resharding a replicated result."""
    if leaf.ndim >= 1:
        return ("dp",) + (None,) * (leaf.ndim - 1)
    return ()


def shard_budgets(budgets, mesh=None):
    """device_put a per-request budget vector onto the active mesh
    (identity off-mesh; replication fallback for non-dividing B)."""
    mesh = mesh if mesh is not None else api.active_mesh()
    if mesh is None:
        return budgets
    return jax.device_put(budgets, NamedSharding(
        mesh, logical_to_mesh(mesh, budgets_pspec(budgets), budgets.shape)))


def shard_bits(bits, mesh=None):
    """device_put a resolved bit table onto the active mesh (identity
    off-mesh); replication fallback covers non-dividing batch sizes."""
    mesh = mesh if mesh is not None else api.active_mesh()
    if mesh is None:
        return bits
    return jax.device_put(bits, NamedSharding(
        mesh, logical_to_mesh(mesh, bits_pspec(bits), bits.shape)))


# ---------------------------------------------------------------------------
# KV / SSM caches
# ---------------------------------------------------------------------------

def _axis_entry(axes: Tuple[str, ...]):
    return axes[0] if len(axes) == 1 else tuple(axes)


def _kv_cache_spec(mesh, shape) -> P:
    """(L, B, KV, S, hd) cache spec.

    dp goes on the batch dim when it divides; a B=1 long-context decode
    shards the SEQUENCE over dp instead (the ring buffer is per-slot, so
    sequence sharding is legal).  tp goes on KV heads when they divide,
    else on the per-head feature dim (small-GQA models).
    """
    L, B, KV, S, hd = shape
    entries: list = [None] * 5
    dp_axes = api.mesh_axes_for(mesh, "dp")
    dp_sz = api.dp_size(mesh)
    if dp_sz > 1:
        if B % dp_sz == 0:
            entries[1] = _axis_entry(dp_axes)
        elif S % dp_sz == 0:
            entries[3] = _axis_entry(dp_axes)
    tp_axes = api.mesh_axes_for(mesh, "tp")
    tp_sz = api.tp_size(mesh)
    if tp_sz > 1:
        if KV % tp_sz == 0:
            entries[2] = _axis_entry(tp_axes)
        elif hd % tp_sz == 0:
            entries[4] = _axis_entry(tp_axes)
    return P(*entries)


def _cache_leaf_spec(mesh, keys: Tuple[str, ...], leaf) -> P:
    name = keys[-1]
    shape = leaf.shape
    if name in ("k", "v") and leaf.ndim == 5:
        return _kv_cache_spec(mesh, shape)
    if name == "kpos" and leaf.ndim == 3:           # (L, B, Sc) per-row
        L, B, Sc = shape                            # positions follow the
        dp_sz = api.dp_size(mesh)                   # k/v batch placement
        if dp_sz > 1 and B % dp_sz == 0:
            return P(None, _axis_entry(api.mesh_axes_for(mesh, "dp")), None)
        return P(None, None, None)
    if name in ("ks", "vs") and leaf.ndim == 4:     # int8 cache scales:
        full = _kv_cache_spec(mesh, shape + (1,))   # (L, B, KV, S) = k/v
        return P(*tuple(full)[:4])                  # minus the head dim
    if name == "ssm" and leaf.ndim >= 3:            # (L, B, H, P, N)
        return logical_to_mesh(
            mesh, (None, "dp", "tp") + (None,) * (leaf.ndim - 3), shape)
    if name == "conv" and leaf.ndim >= 2:           # (L, B, K-1, C)
        spec = [None] * leaf.ndim
        spec[1] = "dp"
        spec[-1] = "tp"
        return logical_to_mesh(mesh, tuple(spec), shape)
    return P(*(None,) * leaf.ndim)                  # kpos etc.


def cache_shardings(cache, mesh, plan=None):
    """Cache shardings; ``plan`` is accepted for call-site symmetry with
    :func:`param_shardings` (a placement plan only moves WEIGHTS — the
    cache's dp-on-batch placement is already what row-parallel scale-out
    execution needs, so the base rules stand unchanged)."""
    del plan
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, _cache_leaf_spec(mesh, _keys(path), leaf)),
        cache)
