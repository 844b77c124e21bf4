"""Packed-int4 GEMM — weight bits as HBM bandwidth (the decode kernel).

On the *memory-bound* side of the roofline (autoregressive decode reads
every weight once per token), weight bits are bandwidth are latency — the
TPU equivalent of the AP's per-bit energy scaling.  This kernel streams
int4 weights packed two-per-byte (half the HBM traffic of int8, a quarter
of bf16) and unpacks in VMEM.

Packing is the *halves* layout (core/bitfluid.pack_int4_halves): output
columns [0, N/2) live in low nibbles, [N/2, N) in high nibbles, so a weight
tile unpacks with a single elementwise nibble-select — no interleave, no
layout change.  The grid's N dimension runs over *logical* columns; the
index map folds column block j onto packed block j % (N/2bn) and the kernel
selects the nibble from the block index.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, wp_ref, s_ref, o_ref, acc_ref, *, k_steps: int,
            n_half_blocks: int, out_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    j = pl.program_id(1)
    # shift, mask and sign extension run on int32 lanes (Mosaic has no
    # 8-bit shifts); only the finished int4 values narrow to int8 for the dot
    wp = wp_ref[...].astype(jnp.int32)                 # (bk, bn) packed bytes
    nib = jnp.where(j < n_half_blocks, wp & 0xF, (wp >> 4) & 0xF)
    w = jnp.where(nib >= 8, nib - 16, nib).astype(jnp.int8)   # sign-extend

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * s_ref[...]).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "bm", "bn", "bk",
                                             "interpret"))
def int4_matmul(x_q: jnp.ndarray, w_packed: jnp.ndarray, scale: jnp.ndarray,
                *, out_dtype=jnp.float32, bm: int = 128, bn: int = 128,
                bk: int = 128, interpret: bool = False) -> jnp.ndarray:
    """(M,K) int8 @ packed (K,N/2) uint8 -> (M,N) out_dtype.

    scale: (1, N) fused per-channel dequant (activation scale folded in).
    N is the logical (unpacked) width; w_packed.shape == (K, N // 2).
    """
    M, K = x_q.shape
    K2, N_half = w_packed.shape
    N = 2 * N_half
    assert K == K2 and scale.shape == (1, N)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0 and N_half % bn == 0
    k_steps = K // bk
    n_half_blocks = N_half // bn

    return pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps,
                          n_half_blocks=n_half_blocks, out_dtype=out_dtype),
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            # fold logical column block j onto its packed block
            pl.BlockSpec((bk, bn),
                         lambda i, j, k: (k, j % n_half_blocks)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_q, w_packed, scale)
