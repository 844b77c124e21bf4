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
  is baked into it).  A layer scan hands it the whole ``(L, K, N)``
  stack and the layer's index as a third SMEM scalar, which the
  weight's index map reads: each tile is DMA'd from the stack itself.  At shift
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
tile.  ops.py sizes the blocks by shape (``ops._bitplane_tiles``) and pads
the activations; a weight is never padded (an unaligned N runs a ragged
last column block).
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


def vmem_bytes(bm: int, bn: int, bk: int, n_planes: int,
               k_minor: bool = False) -> int:
    """Upper estimate of the kernel's VMEM working set, in bytes: the
    double-buffered x, weight and int32 output blocks, the int8 scratch
    (requantized tile, and the planes when ``n_planes < 8``), and the
    int32 temporaries (one row chunk's requant or plane split, and the
    matmul results).  The weight tile is (bk, bn), or (bn, bk) when
    ``k_minor``."""
    tile = bk * bn
    rows, cols = (bn, bk) if k_minor else (bk, bn)
    pipelined = 2 * (bm * bk + tile + 4 * bm * bn)
    scratch = tile * (1 if n_planes == 8 else 1 + n_planes)
    temps = 6 * 4 * _chunk_rows(rows) * cols + 2 * 4 * bm * bn
    return pipelined + scratch + temps


def vmem_limit_bytes(bm: int, bn: int, bk: int, n_planes: int,
                     k_minor: bool = False) -> int:
    """The scoped-VMEM limit the kernel compiles under: its working set
    with a quarter more for the compiler's own scratch."""
    ws = vmem_bytes(bm, bn, bk, n_planes, k_minor)
    return ws + ws // 4 + (1 << 20)


def _dot(x, w, k_minor: bool):
    """x (bm, bk) @ the weight tile: (bk, bn), or (bn, bk) K-minor."""
    return jax.lax.dot_general(
        x, w, dimension_numbers=(((1,), (1 if k_minor else 0,)), ((), ())),
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


def _plane_walk(x_ref, src_ref, planes_ref, o_ref, n_planes: int,
                k_minor: bool) -> None:
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
        o_ref[...] += weight * _dot(x_ref[...], planes_ref[j], k_minor)
        return carry

    jax.lax.fori_loop(0, n_planes, plane, 0)


def _kernel(bits_ref, x_ref, w_ref, o_ref, wq_ref, *planes_ref,
            n_planes: int, k_minor: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    to_bits = bits_ref[0]
    shift = bits_ref[1] - to_bits

    def accumulate(src_ref):
        if n_planes == 8:
            # container width: the 8-plane walk reassembles the int8 word
            # exactly, so it degenerates to the MXU's native int8 matmul
            o_ref[...] += _dot(x_ref[...], src_ref[...], k_minor)
        else:
            _plane_walk(x_ref, src_ref, planes_ref[0], o_ref, n_planes,
                        k_minor)

    @pl.when(shift <= 0)
    def _as_stored():
        accumulate(w_ref)

    @pl.when(shift > 0)
    def _requantized():
        _requant_tile(w_ref, wq_ref, shift, to_bits)
        accumulate(wq_ref)


@functools.partial(jax.jit, static_argnames=("n_planes", "bm", "bn", "bk",
                                             "k_minor", "interpret"))
def bitplane_matmul(x_q: jnp.ndarray, w_q: jnp.ndarray, to_bits, from_bits=8,
                    layer=0, *, n_planes: int = 8, bm: int = 128,
                    bn: int = 128, bk: int = 128, k_minor: bool = False,
                    interpret: bool = False) -> jnp.ndarray:
    """(M, K) int8 @ (K, N) ``from_bits`` container requantized to
    ``to_bits`` (int32 scalars) -> (M, N) int32, plane-serial.

    ``w_q`` may instead be a layer stack (L, K, N): ``layer`` (int32)
    rides in SMEM beside the bits, and the weight's index map picks that
    layer's tiles, so the kernel reads them from the stack as stored and
    nothing copies the layer out first.  ``k_minor``: the weight comes
    transposed, (N, K) or (L, N, K), as a TPU lays out a K-minor stack
    (the transpose of one is free), and each tile multiplies transposed.
    M and K must be multiples of their blocks (ops.py pads the
    activations); N need not be: the grid covers ``ceil(N / bn)`` column
    blocks, and the last block's columns past N are never written to the
    (M, N) result.
    """
    M, K = x_q.shape
    w_dims = w_q.shape[-2:]
    K2, N = w_dims[::-1] if k_minor else w_dims
    assert K == K2, (x_q.shape, w_q.shape)
    assert M % bm == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    assert bn == N or bn % 128 == 0, (N, bn)
    assert 1 <= n_planes <= 8
    tile = (bn, bk) if k_minor else (bk, bn)

    def w_index(i, j, k):
        return (j, k) if k_minor else (k, j)

    if w_q.ndim == 3:
        w_spec = pl.BlockSpec((pl.squeezed,) + tile,
                              lambda i, j, k, s: (s[2],) + w_index(i, j, k))
    else:
        w_spec = pl.BlockSpec(tile, lambda i, j, k, s: w_index(i, j, k))
    scratch = [pltpu.VMEM(tile, jnp.int8)]
    if n_planes < 8:
        scratch.append(pltpu.VMEM((n_planes,) + tile, jnp.int8))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M // bm, pl.cdiv(N, bn), K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, s: (i, k)),
            w_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, s: (i, j)),
        scratch_shapes=scratch,
    )
    scalars = jnp.stack([jnp.asarray(v, jnp.int32)
                         for v in (to_bits, from_bits, layer)])
    return pl.pallas_call(
        functools.partial(_kernel, n_planes=n_planes, k_minor=k_minor),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes(bm, bn, bk, n_planes,
                                              k_minor)),
        interpret=interpret,
    )(scalars, x_q, w_q)
