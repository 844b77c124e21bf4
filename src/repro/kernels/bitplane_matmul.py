"""Bit-plane GEMM — the AP's bit-serial multiply rebuilt for the MXU.

BF-IMNA multiplies by walking ``Mw x Ma`` bit pairs through a compare/write
LUT (cost O(M^2), Table I).  The TPU's MXU is a fixed 8-bit-or-wider
systolic array, so the faithful *algorithmic* analogue walks the weight's
bit planes and issues one int8 matmul per plane:

    y = x_q @ w_q = sum_{j < Mw} 2^j * (x_q @ plane_j)        (sign plane
      carries weight -2^(Mw-1), two's complement)

* ``n_planes`` is a **static** specialization (dispatch-cached in ops.py) —
  lowering a 4-bit layer issues 4 plane matmuls, a 2-bit layer 2: compute
  cost scales linearly with assigned weight bits, the MXU analogue of the
  AP's "MSBs deactivated" energy scaling.
* Activation bits are absorbed by the MXU's native 8-bit path; activation
  fluidity is dyadic requantization of the activations before the kernel.
* Weight fluidity happens here, on the tile: the kernel takes the container
  as stored and its ``(to_bits, from_bits)`` pair in SMEM (scalar
  prefetch, so static and traced bits share one kernel and no bit width
  is baked into it).  At shift
  ``from_bits - to_bits <= 0`` the tile goes to the MXU untouched — no
  per-byte VPU work; above 0 it is requantized in VMEM with
  ``core.bitfluid.requant_shift``'s integer rounding first.  Skipping the
  clamp at shift 0 is exact on the symmetric container grid
  (``core.bitfluid`` states the invariant).

Tiling: grid (M/bm, N/bn, K/bk), K innermost; the int32 output block stays
in VMEM across the K steps and accumulates there.  Requantization and plane
extraction run ``_ROWS`` rows of the VMEM-resident tile at a time into int8
scratch, so HBM traffic is the int8 container once — requantized copies and
planes never reach HBM — and the int32 working set does not grow with the
tile.  ops.py sizes the blocks by shape (``ops._bitplane_tiles``) and pads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROWS = 64          # weight-tile rows requantized / split into planes per step


def _chunk_rows(bk: int) -> int:
    return min(bk, _ROWS)


def vmem_bytes(bm: int, bn: int, bk: int, n_planes: int) -> int:
    """Upper estimate of the kernel's VMEM working set, in bytes: the
    double-buffered x, weight and int32 output blocks, the int8 scratch
    (requantized tile, and the planes when ``n_planes < 8``), and the
    int32 temporaries (one row chunk's requant or plane split, and the
    matmul results)."""
    tile = bk * bn
    pipelined = 2 * (bm * bk + tile + 4 * bm * bn)
    scratch = tile * (1 if n_planes == 8 else 1 + n_planes)
    temps = 6 * 4 * _chunk_rows(bk) * bn + 2 * 4 * bm * bn
    return pipelined + scratch + temps


def vmem_limit_bytes(bm: int, bn: int, bk: int, n_planes: int) -> int:
    """The scoped-VMEM limit the kernel compiles under: its working set
    with a quarter more for the compiler's own scratch."""
    ws = vmem_bytes(bm, bn, bk, n_planes)
    return ws + ws // 4 + (1 << 20)


def _dot(x, w):
    return jax.lax.dot_general(x, w, dimension_numbers=(((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _row_loop(n_rows: int, rows: int, body) -> None:
    def step(c, carry):
        body(pl.ds(pl.multiple_of(c * rows, rows), rows))
        return carry
    jax.lax.fori_loop(0, n_rows // rows, step, 0)


def _requant_tile(w_ref, wq_ref, shift, to_bits) -> None:
    """wq = requant_shift(w, to_bits) for shift > 0 (round half away from
    zero, clamp to the symmetric ``to_bits`` grid)."""
    half = jnp.left_shift(1, shift - 1)
    lim = jnp.left_shift(1, to_bits - 1) - 1

    def body(r):
        qi = w_ref[r, :].astype(jnp.int32)
        rounded = jnp.where(qi >= 0, (qi + half) >> shift,
                            -((half - qi) >> shift))
        wq_ref[r, :] = jnp.clip(rounded, -lim, lim).astype(jnp.int8)

    _row_loop(w_ref.shape[0], _chunk_rows(w_ref.shape[0]), body)


def _plane_walk(x_ref, src_ref, planes_ref, o_ref, n_planes: int) -> None:
    """o += sum_j w_j * (x @ plane_j) over the low ``n_planes``
    two's-complement field of ``src`` — the bit-serial walk, one int8
    matmul per plane (a loop, so the kernel holds one matmul)."""
    mask = (1 << n_planes) - 1

    def split(r):
        field = src_ref[r, :].astype(jnp.int32) & mask
        for j in range(n_planes):
            planes_ref[j, r, :] = ((field >> j) & 1).astype(jnp.int8)

    _row_loop(src_ref.shape[0], _chunk_rows(src_ref.shape[0]), split)

    def plane(j, carry):
        weight = jnp.where(j == n_planes - 1, -(1 << (n_planes - 1)),
                           jnp.left_shift(1, j))
        o_ref[...] += weight * _dot(x_ref[...], planes_ref[j])
        return carry

    jax.lax.fori_loop(0, n_planes, plane, 0)


def _kernel(bits_ref, x_ref, w_ref, o_ref, wq_ref, *planes_ref,
            n_planes: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    to_bits = bits_ref[0]
    shift = bits_ref[1] - to_bits

    def accumulate(src_ref):
        if n_planes == 8:
            # container width: the 8-plane walk reassembles the int8 word
            # exactly, so it degenerates to the MXU's native int8 matmul
            o_ref[...] += _dot(x_ref[...], src_ref[...])
        else:
            _plane_walk(x_ref, src_ref, planes_ref[0], o_ref, n_planes)

    @pl.when(shift <= 0)
    def _as_stored():
        accumulate(w_ref)

    @pl.when(shift > 0)
    def _requantized():
        _requant_tile(w_ref, wq_ref, shift, to_bits)
        accumulate(wq_ref)


@functools.partial(jax.jit, static_argnames=("n_planes", "bm", "bn", "bk",
                                             "interpret"))
def bitplane_matmul(x_q: jnp.ndarray, w_q: jnp.ndarray, to_bits, from_bits=8,
                    *, n_planes: int = 8, bm: int = 128, bn: int = 128,
                    bk: int = 128, interpret: bool = False) -> jnp.ndarray:
    """(M, K) int8 @ (K, N) ``from_bits`` container requantized to
    ``to_bits`` (int32 scalars) -> (M, N) int32, plane-serial.

    Shapes must be multiples of the block sizes (ops.py pads).
    """
    M, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2, (x_q.shape, w_q.shape)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    assert 1 <= n_planes <= 8
    scratch = [pltpu.VMEM((bk, bn), jnp.int8)]
    if n_planes < 8:
        scratch.append(pltpu.VMEM((n_planes, bk, bn), jnp.int8))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, b: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k, b: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, b: (i, j)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_planes=n_planes),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes(bm, bn, bk, n_planes)),
        interpret=interpret,
    )(jnp.stack([jnp.asarray(to_bits, jnp.int32),
                 jnp.asarray(from_bits, jnp.int32)]), x_q, w_q)
