"""Mixture-of-Experts FFN (kimi-k2, moonshot) with expert parallelism.

Capacity-based top-k routing designed to stay memory-sane at 1M-token
global batches: position-in-expert is computed per *choice* (k small
one-hot cumsums of (T, E)), never materializing (T*k, E); dispatch/combine
are scatter-add / gather on an (E, C, d) buffer that shards E over the
``model`` (expert-parallel) axis and C over ``data`` — the sharded
scatter is where XLA emits the token-routing all-to-all.

Per-EXPERT precision: the paper's per-layer granularity maps naturally to
per-expert here (DESIGN.md §4) — ``wbits`` may be a scalar or an (E,)
vector; expert e's GEMMs run at wbits[e].
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro import dist
from repro.core import bitfluid as bf
from repro.kernels import ops as kops
from repro.models import common as cm


def moe_init(key, cfg) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    s = d ** -0.5

    def w(k, shape, sc):
        return (jax.random.normal(k, shape, jnp.float32) * sc).astype(cm.DTYPE)

    p = {
        "router": {"w": w(ks[0], (d, E), s)},
        "experts": {
            "wg": w(ks[1], (E, d, f), s),
            "wu": w(ks[2], (E, d, f), s),
            "wd": w(ks[3], (E, f, d), f ** -0.5),
        },
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["shared"] = {"wg": cm.dense_init(ks[4], d, fs),
                       "wu": cm.dense_init(jax.random.fold_in(ks[4], 1), d, fs),
                       "wd": cm.dense_init(jax.random.fold_in(ks[4], 2), fs, d,
                                           scale=fs ** -0.5)}
    return p


def _expert_ffn(pe, xin, wbits, abits):
    """xin: (E, C, d); per-expert SwiGLU, expert e at wbits[e]."""
    if not isinstance(pe["wg"], dict):                  # train form
        wb = jnp.broadcast_to(jnp.asarray(wbits), (pe["wg"].shape[0],))

        def per_expert(w3, x, b):
            wq = bf.fake_quant(w3.astype(jnp.float32), b, axis=0)
            return (bf.fake_quant(x.astype(jnp.float32), abits) @ wq
                    ).astype(cm.DTYPE)

        g = jax.vmap(per_expert, in_axes=(0, 0, 0))(pe["wg"], xin, wb)
        u = jax.vmap(per_expert, in_axes=(0, 0, 0))(pe["wu"], xin, wb)
        h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
             ).astype(cm.DTYPE)
        return jax.vmap(per_expert, in_axes=(0, 0, 0))(pe["wd"], h, wb)
    # serve form: {"q": (E,d,f) int8, "s": (E,1,f)} — expert stacks run as
    # one batched GEMM through the kernel layer, expert e at wbits[e]
    # (ops.serve_linear_stacked with stack_bits: per-expert weights
    # differ, so the per-expert requant is NOT redundant, unlike per-row
    # bits over shared weights).
    def stacked(pq, x):
        return kops.serve_linear_stacked(
            {"q": pq["q"], "s": pq["s"]}, x, wbits, abits,
            stack_bits=True).astype(cm.DTYPE)

    g = stacked(pe["wg"], xin)
    u = stacked(pe["wu"], xin)
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
         ).astype(cm.DTYPE)
    return stacked(pe["wd"], h)


def _route(p, xf, cfg):
    """Router top-k + load-balance aux.  xf: (T, d)."""
    E, k = cfg.n_experts, cfg.experts_per_token
    logits = cm.apply_linear(p["router"], xf, 16, 16).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    topv, topi = jax.lax.top_k(probs, k)                        # (T, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)
    return topi, topv, aux


def _positions(topi, E, C):
    """Position-in-expert per choice: k cumsums of (T, E) — never (T*k, E).
    Returns (eid, pos, keep) flattened (T*k,)."""
    T, k = topi.shape
    counts = jnp.zeros((E,), jnp.int32)
    pos_list, keep_list = [], []
    for j in range(k):
        oh = jax.nn.one_hot(topi[:, j], E, dtype=jnp.int32)     # (T, E)
        pos_j = jnp.cumsum(oh, axis=0) - 1 + counts[None, :]
        pos_sel = jnp.sum(oh * pos_j, axis=-1)                  # (T,)
        counts = counts + jnp.sum(oh, axis=0)
        pos_list.append(pos_sel)
        keep_list.append(pos_sel < C)
    pos = jnp.stack(pos_list, 1).reshape(-1)                    # (T*k,)
    keep = jnp.stack(keep_list, 1).reshape(-1)
    return topi.reshape(-1), pos, keep


def _dispatch_compute_combine(xf, topi, topv, experts, cfg, wbits, abits, C):
    """Single-device dispatch -> expert FFN -> combine.  xf: (T, d)."""
    T, d = xf.shape
    E, k = experts_E(experts), cfg.experts_per_token
    eid, pos, keep = _positions(topi, E, C)
    gate = (topv.reshape(-1) * keep).astype(jnp.float32)
    xr = jnp.repeat(xf, k, axis=0)                              # (T*k, d)
    xr = dist.constrain(xr, ("dp", None))
    pos_c = jnp.where(keep, pos, 0)
    buf = jnp.zeros((E, C, d), xf.dtype)
    buf = buf.at[eid, pos_c].add(
        jnp.where(keep[:, None], xr, 0), mode="drop")
    buf = dist.constrain(buf, ("tp", "dp", None))
    out_buf = _expert_ffn(experts, buf, wbits, abits)           # (E, C, d)
    yk = out_buf[eid, pos_c] * gate[:, None]                    # (T*k, d)
    return jnp.sum(yk.reshape(T, k, d), axis=1).astype(cm.DTYPE)


def experts_E(experts) -> int:
    wg = experts["wg"]
    return (wg["q"] if isinstance(wg, dict) else wg).shape[0]


def _apply_moe_shard_map(p, xf, topi, topv, cfg, wbits, abits, mesh, C_shard):
    """Expert-parallel dispatch under shard_map (DESIGN.md §5):

    tokens shard over dp; experts shard over `model`; each device routes
    its LOCAL tokens to its LOCAL experts (pure local scatter — no sharded
    scatter for SPMD to mangle), FSDP-gathers its expert weights over
    `data`, runs the FFN, and a single psum over `model` combines each
    token's k expert contributions.  Collectives per layer: one (E_loc,
    d, f) all-gather + one (T_loc, d) all-reduce — vs the auto-partitioned
    scatter's full-buffer all-reduces (the kimi 84 TB/device baseline).

    Works for both train-form (bare (E,d,f) arrays) and serve-form
    ({"q": int8, "s": scales}) expert stacks: every 3-D leaf with a real
    middle axis is FSDP-sharded there (wg/wu on d, wd on f), scales
    (E,1,f) ride along replicated over dp."""
    from jax.sharding import PartitionSpec as P

    names = set(mesh.axis_names)
    dp_ax = tuple(a for a in ("pod", "data") if a in names)
    E, k = cfg.n_experts, cfg.experts_per_token
    tp = mesh.shape["model"]
    E_loc = E // tp
    ex = p["experts"]

    def _is_big(leaf) -> bool:
        return leaf.ndim == 3 and leaf.shape[1] > 1

    def local(xf_b, topi_b, topv_b, ex_b):
        rank = jax.lax.axis_index("model")
        if dp_ax:
            ex_b = jax.tree.map(
                lambda l: (jax.lax.all_gather(l, dp_ax, axis=1, tiled=True)
                           if _is_big(l) else l), ex_b)
        # re-index global expert ids onto this rank's slot [0, E_loc)
        local_i = topi_b - rank * E_loc
        mine = (local_i >= 0) & (local_i < E_loc)
        li = jnp.where(mine, local_i, E_loc)     # E_loc = dummy overflow slot
        eid, pos, keep = _positions(li, E_loc + 1, C_shard)
        keep &= mine.reshape(-1)
        gate = (topv_b.reshape(-1) * keep).astype(jnp.float32)
        T_loc, d = xf_b.shape
        xr = jnp.repeat(xf_b, k, axis=0)
        pos_c = jnp.where(keep, pos, 0)
        eid_c = jnp.where(keep, eid, 0)
        buf = jnp.zeros((E_loc, C_shard, d), xf_b.dtype)
        buf = buf.at[eid_c, pos_c].add(
            jnp.where(keep[:, None], xr, 0), mode="drop")
        out_buf = _expert_ffn(ex_b, buf, wbits, abits)
        yk = out_buf[eid_c, pos_c] * gate[:, None]
        y = jnp.sum(yk.reshape(T_loc, k, d), axis=1)
        return jax.lax.psum(y, "model").astype(cm.DTYPE)

    dp = dp_ax if len(dp_ax) > 1 else (dp_ax[0] if dp_ax else None)
    ex_specs = jax.tree.map(
        lambda l: P("model", dp, None) if _is_big(l)
        else P("model", None, None), ex)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None), P(dp, None), P(dp, None), ex_specs),
        out_specs=P(dp, None),
        check_vma=False,
    )(xf, topi, topv, ex)


def apply_moe(p, x, cfg, wbits=8, abits=8) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss).  Top-k capacity routing.

    Under an active mesh with E % tp == 0 (train form), dispatch runs the
    explicit shard_map expert-parallel path; otherwise the single-device
    path (CPU tests, serving with few devices)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    T = B * S
    xf = x.reshape(T, d)
    topi, topv, aux = _route(p, xf, cfg)

    mesh = dist.api.active_mesh()
    use_sm = (mesh is not None and "model" in mesh.shape
              and E % mesh.shape["model"] == 0)
    if use_sm:
        names = set(mesh.axis_names)
        dp_sz = 1
        for a in ("pod", "data"):
            if a in names:
                dp_sz *= mesh.shape[a]
        use_sm = (T % dp_sz == 0 and d % dp_sz == 0
                  and cfg.d_ff % dp_sz == 0)
        if use_sm:
            T_loc = T // dp_sz
            C_shard = max(int(T_loc * k / E * cfg.capacity_factor), 4)
            C_shard = -(-C_shard // 8) * 8
            y = _apply_moe_shard_map(p, xf, topi, topv, cfg, wbits, abits,
                                     mesh, C_shard)
    if not use_sm:
        C = max(int(T * k / E * cfg.capacity_factor), 1)
        C = -(-C // 512) * 512 if T >= 4096 else C
        y = _dispatch_compute_combine(xf, topi, topv, p["experts"], cfg,
                                      wbits, abits, C)

    if "shared" in p:
        # shared expert runs at the max of the per-expert bits (scalar)
        wb_s = wbits if jnp.ndim(wbits) == 0 else jnp.max(wbits)
        g = cm.apply_linear(p["shared"]["wg"], xf, wb_s, abits)
        u = cm.apply_linear(p["shared"]["wu"], xf, wb_s, abits)
        h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
             ).astype(cm.DTYPE)
        y = y + cm.apply_linear(p["shared"]["wd"], h, wb_s, abits)
    return y.reshape(B, S, d), aux
