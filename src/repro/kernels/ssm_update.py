"""Single-token Mamba-2 state update, in place — the decode hot spot of a
state-space layer.

For every live slot ``b`` of one layer ``l`` of the stacked state
``state (L, B, H, P, N)`` (float32):

    h    = dA[b, h] * state[l, b, h] + (dt[b, h] * x[b, h]) (outer) B[b]
    y    = h . C[b]                                (contracted over N)

The state is read once and written back into the same HBM buffer
(``input_output_aliases``): the caller carries the whole stack through
its layer scan and this kernel touches only layer ``l``'s block of each
slot.  Grid: one step per slot, the slot's whole (H, P, N) block in VMEM
(2 MiB at H = P = 64, N = 128), heads walked in a loop so each update is
one (P, N) tile with N on the lanes.

Per-head vectors arrive transposed, ``(B, P, H)``, so a head's values are
a column (picked by a lane mask and a lane sum: exact, one non-zero).
Empty slots (``live == 0``) are neither read nor written: their grid
steps point at the nearest live slot's block, which the pipeline then
neither fetches again nor writes back early, and their ``y`` is zero.
With no live slot at all, slot 0's block is copied through unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT = 32 * 1024 * 1024


def _kernel(layer_ref, slot_ref, run_ref, keep_ref, u_ref, a_ref, b_ref,
            c_ref, s_ref, y_ref, o_ref, *, n_heads: int):
    del layer_ref, slot_ref
    i = pl.program_id(0)

    @pl.when(run_ref[i] == 1)
    def _update():
        brow = b_ref[0]                         # (1, N)
        crow = c_ref[0]                         # (1, N)
        u_t = u_ref[0]                          # (P, H): dt * x, transposed
        a_t = a_ref[0]                          # (P, H): exp(a * dt)
        lane = jax.lax.broadcasted_iota(jnp.int32, u_t.shape, 1)

        def head(h, y_t):
            sel = lane == h
            ucol = jnp.sum(jnp.where(sel, u_t, 0.0), axis=1, keepdims=True)
            acol = jnp.sum(jnp.where(sel, a_t, 0.0), axis=1, keepdims=True)
            new = s_ref[0, 0, h] * acol + ucol * brow          # (P, N)
            o_ref[0, 0, h] = new
            ycol = jnp.sum(new * crow, axis=1, keepdims=True)  # (P, 1)
            return jnp.where(sel, ycol, y_t)

        y_ref[0] = jax.lax.fori_loop(0, n_heads, head,
                                     jnp.zeros(u_t.shape, jnp.float32))

    @pl.when(run_ref[i] == 0)
    def _idle():
        y_ref[...] = jnp.zeros_like(y_ref)

        @pl.when(keep_ref[i] == 1)
        def _copy():
            o_ref[...] = s_ref[...]


def slot_schedule(live: jnp.ndarray):
    """(slot, run, keep) int32 per grid step from ``live`` (B,): a dead
    step points at the last live slot before it (else the first live
    one), so consecutive steps share a block and nothing is moved for
    it; ``keep`` marks the one step that copies a block through when no
    slot is live."""
    live = jnp.asarray(live).astype(bool)
    B = live.shape[0]
    idx = jnp.arange(B, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(live, idx, -1), axis=0)
    first = jnp.argmax(live).astype(jnp.int32)
    slot = jnp.where(last >= 0, last, first).astype(jnp.int32)
    keep = (~jnp.any(live)) & (idx == 0)
    return slot, live.astype(jnp.int32), keep.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_update(state, layer, u_t, a_t, b_row, c_row, live, *,
               interpret: bool = False):
    """state (L, B, H, P, N) f32, updated in place at ``layer``;
    u_t = dt * x and a_t = exp(a * dt), both (B, P, H) f32; b_row, c_row
    (B, 1, N) f32; live (B,).  Returns (y_t (B, P, H) f32, state)."""
    L, B, H, P, N = state.shape
    slot, run, keep = slot_schedule(live)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    def per_slot(i, layer_ref, slot_ref, run_ref, keep_ref):
        return (slot_ref[i], 0, 0)

    def state_block(i, layer_ref, slot_ref, run_ref, keep_ref):
        return (layer_ref[0], slot_ref[i], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, P, H), per_slot),
            pl.BlockSpec((1, P, H), per_slot),
            pl.BlockSpec((1, 1, N), per_slot),
            pl.BlockSpec((1, 1, N), per_slot),
            pl.BlockSpec((1, 1, H, P, N), state_block),
        ],
        out_specs=[
            pl.BlockSpec((1, P, H), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, 1, H, P, N), state_block),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_heads=H),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((B, P, H), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="ssm_update",
    )(layer, slot, run, keep, u_t, a_t, b_row, c_row, state)
