"""Public kernel API: padding, dispatch (Pallas-TPU vs XLA ref), caching.

This module is the **single compute path** for serve-form math: every
quantized GEMM in ``models/`` reaches Pallas (TPU) or the jnp refs (CPU,
dry-run) only through the dispatchers here — ``serve_linear`` for int8 /
packed-int4 containers (scalar, traced, or per-row bits), and
``flash_attention`` for long-sequence attention.

``use_pallas()`` is True only on real TPU backends; elsewhere (CPU test
runs, and inside the 512-device dry-run) the mathematically identical ref
path lowers through XLA, so compiled-artifact analysis reflects the same
algorithm.  Kernel *numerics* are validated against ref in
tests/test_kernels.py with interpret=True.  Two test-only switches
override the backend's choice: ``REPRO_PALLAS=interpret`` in the
environment routes every dispatcher through interpret-mode Pallas (the CI
kernel job), and :func:`set_force_pallas` forces either path in-process.
:func:`pallas_overrides` names whichever is active, so a run that must
prove the TPU path (``chip_smoke.py``) can refuse them.

Per-precision specializations are cached by (n_planes, block shape) via
jit's static-arg cache: switching a layer between 2/4/8 bits after warmup
costs no recompilation — the dispatch-cache realization of bit fluidity.
The serve GEMM's blocks are a function of its shape and plane count
(``_bitplane_tiles``): each weight is one kernel pass, with no XLA op
reading it first.

Bit-grouped batch execution
---------------------------
Per-request precision hands ``serve_linear`` a ``(B,)`` bit vector.  The
naive realization (one weight requantization per row) does O(B·K·N) weight
work for at most a handful of distinct bit-widths.  Instead, the grouped
path runs one batch GEMM per *family* in the static ``BIT_FAMILIES`` set
(each at a static plane count — the plane-serial kernel's cost ∝ bits —
requantizing the container on its VMEM tiles), and gathers each
row's result from its family's accumulator: O(G·K·N) weight work,
zero-retrace (family membership is data).  ``set_bit_families`` /
``bit_families`` narrow the set to the precisions a serving policy can
actually emit; rows whose bits fall between families snap UP to the next
family, and rows ABOVE the largest family clamp down to it — so a family
set must always include its policy's widest bit-width (engines derive it
from the controller, which guarantees this; results are bit-exact
whenever the bits are in the set).  The historical
per-row vmap path is kept behind ``set_row_dispatch("vmap")`` as the
benchmark baseline.
"""
from __future__ import annotations

import contextlib
import os
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitfluid as bf
from repro.kernels import ref as kref
from repro.kernels.bitplane_matmul import bitplane_matmul as _bitplane_pallas
from repro.kernels.bitplane_matmul import vmem_bytes as _bitplane_vmem
from repro.kernels.quant_matmul import quant_matmul as _quant_pallas
from repro.kernels.int4_matmul import int4_matmul as _int4_pallas
from repro.kernels.ssm_update import ssm_update as _ssm_update_pallas

INTERPRET_ENV = os.environ.get("REPRO_PALLAS", "").lower() == "interpret"
_FORCE: Optional[bool] = True if INTERPRET_ENV else None
_INTERPRET = INTERPRET_ENV

# Distinct weight bit-widths the grouped per-row path specializes for.
BIT_FAMILIES = (2, 3, 4, 6, 8)
_families: Sequence[int] = BIT_FAMILIES
_row_dispatch = "grouped"


def set_force_pallas(v: Optional[bool], interpret: Optional[bool] = None
                     ) -> None:
    global _FORCE, _INTERPRET
    _FORCE = v
    if interpret is not None:
        _INTERPRET = interpret


def use_pallas() -> bool:
    if _FORCE is not None:
        return _FORCE
    return jax.default_backend() == "tpu"


def pallas_overrides() -> list:
    """The test-only switches currently overriding the backend's choice
    of kernel path (empty on a plain run)."""
    out = []
    if os.environ.get("REPRO_PALLAS"):
        out.append(f"REPRO_PALLAS={os.environ['REPRO_PALLAS']}")
    if _FORCE is not None:
        out.append(f"set_force_pallas({_FORCE})")
    if _INTERPRET:
        out.append("interpret mode")
    return out


def _interp(flag: bool) -> bool:
    return bool(flag or _INTERPRET)


def set_bit_families(fams: Sequence[int]) -> None:
    """Set the static family set for grouped per-row dispatch.

    Values clamp into [1, 8] (the int8 container width); serving engines
    derive this from their controller's registered configurations, so the
    grouped path runs exactly one GEMM per precision the policy can emit.
    The set MUST contain the widest bit-width rows can carry: bits between
    families snap up, but bits above the largest family clamp DOWN to it
    (there is no wider GEMM to snap up to).
    """
    global _families
    vals = tuple(sorted({min(max(int(f), 1), 8) for f in fams}))
    if not vals:
        raise ValueError("bit family set must be non-empty")
    _families = vals


def get_bit_families():
    return tuple(_families)


@contextlib.contextmanager
def bit_families(fams: Sequence[int]):
    """Scoped family set (trace-time property of the jitted caller)."""
    global _families
    prev = _families
    set_bit_families(fams)
    try:
        yield
    finally:
        _families = prev


_token_scales = False


@contextlib.contextmanager
def token_scale_mode():
    """Per-token activation scales in the per-row serve path (trace-time).

    The default per-row path reduces activation amax over every axis past
    the batch axis — one scale per request, exactly what a ``(B, 1, K)``
    single-token decode step computes.  A speculative verify chunk runs
    ``(B, U, K)`` token positions in one forward; sharing one scale across
    the U tokens would change numerics vs running them sequentially.
    Under this context the amax reduction keeps every leading axis and
    reduces only the feature axis, so each token row of the flattened
    ``(B*U, K)`` grouped GEMM carries the same scale sequential decode
    would give it — the chunked forward stays bit-identical to U
    single-token steps.
    """
    global _token_scales
    prev = _token_scales
    _token_scales = True
    try:
        yield
    finally:
        _token_scales = prev


def set_row_dispatch(mode: str) -> None:
    """'grouped' (default) or 'vmap' (the per-row baseline, kept for
    benchmarks/parity tests).  Read at trace time."""
    global _row_dispatch
    if mode not in ("grouped", "vmap"):
        raise ValueError(f"row dispatch must be 'grouped' or 'vmap', "
                         f"got {mode!r}")
    _row_dispatch = mode


def get_row_dispatch() -> str:
    return _row_dispatch


@contextlib.contextmanager
def row_dispatch(mode: str):
    global _row_dispatch
    prev = _row_dispatch
    set_row_dispatch(mode)
    try:
        yield
    finally:
        _row_dispatch = prev


def _pad_to(x: jnp.ndarray, mults) -> jnp.ndarray:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def _block_dim(d: int) -> int:
    """128 for MXU-sized dims; small dims shrink to the next power of two
    (floor 8) so a (64, 32) tail GEMM doesn't pad every operand to 128."""
    if d >= 128:
        return 128
    return max(8, 1 << (max(d - 1, 1)).bit_length())


def _blocks_for(M: int, N: int, K: int):
    """MXU-aligned blocks; every small dim shrinks to avoid wasteful
    padding (M, N, and K alike — N/K were previously pinned at 128)."""
    return _block_dim(M), _block_dim(N), _block_dim(K)


# ---------------------------------------------------------------------------
# Serve GEMM tiles: sized by shape, so each weight is one kernel pass.
# ---------------------------------------------------------------------------

_BM_MAX = 512             # rows per block: a prefill row reads each weight once
_BN_MAX = 512             # lane-dense column block; N >= 1024 keeps 2+ blocks
_W_TILE_BYTES = 4 << 20   # int8 weight tile moved per grid step
_VMEM_BUDGET = 32 << 20   # the kernel's working set (v5e VMEM: 128 MiB)


class Tiles(NamedTuple):
    bm: int
    bn: int
    bk: int
    mp: int               # padded dims: multiples of the blocks
    np: int
    kp: int


def _round_up(d: int, m: int) -> int:
    return -(-d // m) * m


def _lane_blocks(d: int, cap: Optional[int]):
    """(padded d, candidate blocks, largest first): multiples of 128 that
    divide d rounded up to 128, so an aligned dim is never padded (and no
    XLA op copies a weight before the kernel); a dim under 128 is one
    block, as ``_block_dim`` sizes it."""
    if d < 128:
        b = _block_dim(d)
        return b, [b]
    lanes = _round_up(d, 128) // 128
    return lanes * 128, [128 * m for m in range(lanes, 0, -1)
                         if lanes % m == 0 and (cap is None or 128 * m <= cap)]


def _bitplane_tiles(M: int, N: int, K: int, n_planes: int) -> Tiles:
    """Blocks for the serve GEMM kernel, a function of its shape alone.

    bm is the whole of M (rounded up to the int8 sublane tile of 32) up
    to 512; bk is the whole of K wherever the tile fits; bn is the widest
    lane-dense divisor of N up to 512 whose int8 tile stays within 4 MiB.
    Every pair must keep the working set (``vmem_bytes``, which counts the
    plane walk's scratch at ``n_planes < 8``) within the budget; bk shrinks
    only when no bn fits.  qwen3-4b's decode layer: 65 grid steps."""
    nb = -(-M // _BM_MAX)
    bm = _round_up(-(-M // nb), 32)
    np_, bns = _lane_blocks(N, _BN_MAX)
    kp, bks = _lane_blocks(K, None)
    for bk in bks:
        for bn in bns:
            if (bk * bn <= _W_TILE_BYTES and
                    _bitplane_vmem(bm, bn, bk, n_planes) <= _VMEM_BUDGET):
                return Tiles(bm, bn, bk, nb * bm, np_, kp)
    return Tiles(bm, bns[-1], bks[-1], nb * bm, np_, kp)


def _bitplane(x_q, w, to_bits, *, n_planes, from_bits, interpret):
    M, K = x_q.shape
    N = w.shape[1]
    t = _bitplane_tiles(M, N, K, n_planes)
    xp = _pad_to(x_q, (t.mp, t.kp))
    wp = _pad_to(w, (t.kp, t.np))
    out = _bitplane_pallas(xp, wp, to_bits, from_bits, n_planes=n_planes,
                           bm=t.bm, bn=t.bn, bk=t.bk, interpret=interpret)
    return out[:M, :N]


def bitplane_matmul(x_q: jnp.ndarray, w_q: jnp.ndarray, *, n_planes: int = 8,
                    interpret: bool = False) -> jnp.ndarray:
    """int8 (M,K) @ int8-container (K,N) -> int32 (M,N), plane-serial over
    the container as stored (no requantization)."""
    interpret = _interp(interpret)
    if not (use_pallas() or interpret):
        return kref.bitplane_matmul_ref(x_q, w_q, n_planes)
    return _bitplane(x_q, w_q, 8, n_planes=n_planes, from_bits=8,
                     interpret=interpret)


def quant_matmul(x_q: jnp.ndarray, w_q: jnp.ndarray, scale: jnp.ndarray,
                 bias: Optional[jnp.ndarray] = None, *, act: str = "none",
                 out_dtype=jnp.float32, interpret: bool = False) -> jnp.ndarray:
    """int8 (M,K) @ int8 (K,N) with fused per-channel dequant epilogue."""
    interpret = _interp(interpret)
    M, K = x_q.shape
    N = w_q.shape[1]
    scale = jnp.broadcast_to(jnp.asarray(scale, jnp.float32), (1, N))
    bias = (jnp.zeros((1, N), jnp.float32) if bias is None
            else jnp.broadcast_to(jnp.asarray(bias, jnp.float32), (1, N)))
    if not (use_pallas() or interpret):
        return kref.quant_matmul_ref(x_q, w_q, scale, bias, act, out_dtype)
    bm, bn, bk = _blocks_for(M, N, K)
    xp = _pad_to(x_q, (bm, bk))
    wp = _pad_to(w_q, (bk, bn))
    sp = _pad_to(scale, (1, bn))
    bp = _pad_to(bias, (1, bn))
    out = _quant_pallas(xp, wp, sp, bp, act=act, out_dtype=out_dtype,
                        bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:M, :N]


def int4_matmul(x_q: jnp.ndarray, w_packed: jnp.ndarray, scale: jnp.ndarray,
                *, out_dtype=jnp.float32, interpret: bool = False) -> jnp.ndarray:
    """int8 (M,K) @ halves-packed uint8 (K,N/2) with fused dequant.

    Invalid operand shapes raise ``ValueError``.  K and the packed width
    are never padded (padding packed columns would split the low/high
    nibble halves): a dim that does not tile in 128 takes one full-width
    block.  The TPU needs lane-aligned column blocks, so there a packed
    width N/2 that is not a multiple of 128 raises with the shapes.
    """
    interpret = _interp(interpret)
    M, K = x_q.shape
    if w_packed.ndim != 2 or w_packed.shape[0] != K:
        raise ValueError(
            f"int4_matmul: packed weights {w_packed.shape} do not match "
            f"activations {x_q.shape} on K={K}")
    N = 2 * w_packed.shape[1]
    scale = jnp.asarray(scale, jnp.float32)
    if scale.size not in (1, N):
        raise ValueError(
            f"int4_matmul: scale {scale.shape} is not broadcastable to "
            f"(1, {N}) for packed weights {w_packed.shape}")
    scale = jnp.broadcast_to(scale.reshape(1, -1), (1, N))
    if not (use_pallas() or interpret):
        return kref.int4_matmul_ref(x_q, w_packed, scale, out_dtype)
    bm = _block_dim(M)
    bk = 128 if K % 128 == 0 else K
    bn = 128 if (N // 2) % 128 == 0 else N // 2
    if bn % 128 and not interpret:
        raise ValueError(
            f"int4_matmul: packed weights {w_packed.shape} (N={N}) need "
            f"N/2 to be a multiple of 128 for the TPU kernel")
    xp = _pad_to(x_q, (bm, bk))
    out = _int4_pallas(xp, w_packed, scale, out_dtype=out_dtype,
                       bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# Serve-form linears — the models' quantized compute path.
# ---------------------------------------------------------------------------

def _static_bits(b) -> Optional[int]:
    """Python int when ``b`` is a compile-time constant, else None."""
    if isinstance(b, (int, np.integer)) and not isinstance(b, bool):
        return int(b)
    return None


def int8_accum(x_q: jnp.ndarray, w: jnp.ndarray, to_bits, *,
               from_bits: int = 8, interpret: bool = False) -> jnp.ndarray:
    """int8 (M,K) @ a ``from_bits`` container (K,N) requantized to
    ``to_bits`` -> int32 (M,N), through the kernel layer.

    Static ``to_bits`` runs the plane-serial kernel at exactly that many
    bit planes (TPU cost ∝ assigned bits); traced bits run the
    container-width path (the 8-plane walk lowers to one native int8 MXU
    matmul).  The kernel takes the container as stored and requantizes
    each tile in VMEM only at a positive shift, so no XLA op reads the
    weight first; the ref path requantizes in XLA."""
    tb = _static_bits(to_bits)
    n = 8 if tb is None else min(max(tb, 1), 8)
    interpret = _interp(interpret)
    if not (use_pallas() or interpret):
        return kref.bitplane_matmul_ref(
            x_q, bf.requant_shift(w, to_bits, from_bits=from_bits), n)
    return _bitplane(x_q, w, to_bits, n_planes=n, from_bits=from_bits,
                     interpret=interpret)


def _epilogue(acc2, lead, x_scale, w_s, bias):
    """f32(acc) * x_scale * w_s (+ bias) — fixed multiply order, identical
    to the historical inline serve math (parity-tested bit-exact)."""
    y = acc2.astype(jnp.float32).reshape(*lead, -1) * x_scale * w_s
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y


def _container_linear(x, qw, s, bias, *, from_bits, wbits, abits, interpret):
    x2 = x.astype(jnp.float32)
    x_scale = bf.symmetric_scale(x2, abits)           # per-tensor scalar
    x_q = bf.quantize(x2, x_scale, abits)
    w_s = bf.effective_scale(s, wbits, from_bits=from_bits)
    acc = int8_accum(x_q.reshape(-1, x.shape[-1]), qw, wbits,
                     from_bits=from_bits, interpret=interpret)
    return _epilogue(acc, x.shape[:-1], x_scale, w_s, bias)


def quant_linear(x: jnp.ndarray, q: jnp.ndarray, s: jnp.ndarray,
                 bias: Optional[jnp.ndarray] = None, *, wbits=8, abits=8,
                 interpret: bool = False) -> jnp.ndarray:
    """float (..., K) @ int8-container {q (K,N), s (1,N)} -> f32 (..., N).

    Dyadic requantization to ``wbits`` + dynamic ``abits`` activation
    quantization; bits may be Python ints (static → plane-serial kernel)
    or traced scalars (zero-recompilation switch)."""
    return _container_linear(x, q, s, bias, from_bits=8, wbits=wbits,
                             abits=abits, interpret=_interp(interpret))


def int4_linear(x: jnp.ndarray, q4: jnp.ndarray, s: jnp.ndarray,
                bias: Optional[jnp.ndarray] = None, *, wbits=8, abits=8,
                interpret: bool = False) -> jnp.ndarray:
    """float (..., K) @ packed-int4 container {q4 (K,N/2), s (1,N)}.

    With static ``wbits >= 4`` on the Pallas path, requantization is the
    identity and the packed kernel streams nibbles straight from HBM (half
    the weight traffic); the dequant epilogue stays outside the kernel in
    canonical order, so results match the unpacked path exactly (the int4
    accumulator magnitude is < 2^24 for any practical K, hence f32-exact).
    Otherwise the container unpacks and takes the shared requant path.
    """
    interpret = _interp(interpret)
    wb = _static_bits(wbits)
    if wb is not None and wb >= 4 and (use_pallas() or interpret):
        N = 2 * q4.shape[-1]
        x2 = x.astype(jnp.float32)
        x_scale = bf.symmetric_scale(x2, abits)
        x_q = bf.quantize(x2, x_scale, abits)
        acc = int4_matmul(x_q.reshape(-1, x.shape[-1]), q4,
                          jnp.ones((1, N), jnp.float32),
                          out_dtype=jnp.float32, interpret=interpret)
        return _epilogue(acc, x.shape[:-1], x_scale,
                         jnp.asarray(s, jnp.float32), bias)
    return _container_linear(x, bf.unpack_int4_halves(q4), s, bias,
                             from_bits=4, wbits=wbits, abits=abits,
                             interpret=interpret)


def serve_linear(p: dict, x: jnp.ndarray, wbits=8, abits=8, *,
                 interpret: bool = False) -> jnp.ndarray:
    """Serve-form linear dispatch: {"q","s"[,"b"]} or {"q4","s"[,"b"]}.

    ``wbits``/``abits`` scalars (Python ints or traced) take the container
    path; ``(B,)`` vectors (per-request precision) take the bit-grouped
    batch path (or the vmap baseline under ``set_row_dispatch("vmap")``).
    Returns float32; callers cast to their activation dtype.
    """
    if getattr(wbits, "ndim", 0) >= 1 or getattr(abits, "ndim", 0) >= 1:
        return _serve_linear_rows(p, x, wbits, abits, _interp(interpret))
    bias = p.get("b")
    if "q4" in p:
        return int4_linear(x, p["q4"], p["s"], bias, wbits=wbits,
                           abits=abits, interpret=interpret)
    return quant_linear(x, p["q"], p["s"], bias, wbits=wbits, abits=abits,
                        interpret=interpret)


def serve_linear_stacked(p: dict, x: jnp.ndarray, wbits=8, abits=8, *,
                         stack_bits: bool = False,
                         interpret: bool = False) -> jnp.ndarray:
    """Stacked serve-form linears: containers carry a leading stack axis.

    ``p``: ``{"q": (G, K, N), "s": (G, 1, N)}`` — G independent weight
    matrices applied slice-wise to ``x`` ``(G, ..., K)`` in ONE batched
    GEMM (MoE expert stacks, grouped-conv group stacks) instead of a
    per-slice Python loop.  Each slice's weights differ, so the
    per-slice requant is NOT redundant (unlike per-row bits over shared
    weights); every slice still reaches the kernel layer through
    :func:`serve_linear` under vmap.

    ``stack_bits=False`` (default): ``wbits`` is shared by every stack —
    a scalar, or a per-row ``(B,)`` vector when ``x`` is ``(G, B, ...,
    K)`` (each slice then takes the bit-grouped batch path).
    ``stack_bits=True``: ``wbits`` is a ``(G,)`` vector, one width per
    stack (MoE per-expert precision).  Biases are not stacked — callers
    apply a full-width bias after recombining slices.
    """
    interpret = _interp(interpret)
    if stack_bits:
        wb = jnp.broadcast_to(jnp.asarray(wbits, jnp.int32), (x.shape[0],))
        return jax.vmap(
            lambda pp, xx, b: serve_linear(pp, xx, b, abits,
                                           interpret=interpret))(p, x, wb)
    return jax.vmap(
        lambda pp, xx: serve_linear(pp, xx, wbits, abits,
                                    interpret=interpret))(p, x)


def _family_index(wb: jnp.ndarray, fams) -> jnp.ndarray:
    """Index of the smallest family >= wb (clamped into the family range) —
    exact whenever wb is in the set, snap-up otherwise."""
    bounds = jnp.asarray(fams, jnp.int32)
    clipped = jnp.clip(jnp.asarray(wb, jnp.int32), bounds[0], bounds[-1])
    return jnp.searchsorted(bounds, clipped, side="left").astype(jnp.int32)


def _serve_linear_rows(p, x, wbits, abits, interpret):
    """Per-row precision: grouped (one GEMM per static bit family) or the
    vmap baseline (one weight requant per row)."""
    B = x.shape[0]
    wb = jnp.broadcast_to(jnp.asarray(wbits, jnp.int32), (B,))
    ab = jnp.broadcast_to(jnp.asarray(abits, jnp.int32), (B,))
    if _row_dispatch == "vmap":
        return jax.vmap(
            lambda xr, w, a: serve_linear(p, xr, w, a, interpret=interpret)
        )(x, wb, ab)

    if "q4" in p:
        qw, from_bits = bf.unpack_int4_halves(p["q4"]), 4
    else:
        qw, from_bits = p["q"], 8
    K = x.shape[-1]
    lead = x.shape[:-1]
    x2 = x.astype(jnp.float32)
    # per-row dynamic activation quantization at per-row abits (elementwise
    # — activations never need grouping); token_scale_mode keeps one scale
    # per token position instead of one per request (verify chunks)
    if _token_scales:
        axes = (x2.ndim - 1,)
    else:
        axes = tuple(range(1, x2.ndim))
    amax = jnp.max(jnp.abs(x2), axis=axes, keepdims=True)   # (B, 1, ..., 1)
    ab_b = ab.reshape((B,) + (1,) * (x2.ndim - 1))
    lim = bf.qmax(ab_b)
    x_scale = jnp.maximum(amax, 1e-8) / lim
    x_q = jnp.clip(jnp.round(x2 / x_scale), -lim, lim).astype(bf.INT_DTYPE)
    xq2 = x_q.reshape(-1, K)                                # (R, K)
    R = xq2.shape[0]

    # one grouped GEMM (requantizing on the tile) per distinct family —
    # families above the container width collapse (requant 4->6 == 4->4
    # for a q4 container)
    fams = tuple(_families)
    eff = [min(f, from_bits) for f in fams]
    uniq = sorted(set(eff))
    accs, scales = [], []
    for f in uniq:
        accs.append(int8_accum(xq2, qw, f, from_bits=from_bits,
                               interpret=interpret))
        scales.append(jnp.broadcast_to(
            jnp.asarray(bf.effective_scale(p["s"], f, from_bits=from_bits),
                        jnp.float32).reshape(1, -1), (1, accs[-1].shape[-1])))
    acc_stack = jnp.stack(accs)                             # (G, R, N)
    ws_stack = jnp.concatenate(scales, axis=0)              # (G, N)

    # scatter rows back: gather each row's accumulator from its family
    remap = jnp.asarray([uniq.index(e) for e in eff], jnp.int32)
    fam_of_row = remap[_family_index(wb, fams)]              # (B,)
    rows_per_b = R // B
    idx_r = jnp.repeat(fam_of_row, rows_per_b)               # (R,)
    acc = acc_stack[idx_r, jnp.arange(R)]                    # (R, N)
    w_s = ws_stack[idx_r]                                    # (R, N)
    xs_flat = jnp.broadcast_to(x_scale, x2.shape[:-1] + (1,)).reshape(R, 1)
    y = acc.astype(jnp.float32) * xs_flat * w_s
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y.reshape(lead + (y.shape[-1],))


# ---------------------------------------------------------------------------
# End-to-end fluid linear: quantize activations, walk planes, dequantize.
# ---------------------------------------------------------------------------

def fluid_linear(x: jnp.ndarray, w_q: jnp.ndarray, w_scale: jnp.ndarray,
                 *, wbits: int = 8, abits: int = 8,
                 interpret: bool = False) -> jnp.ndarray:
    """float (..., K) @ int8-container (K, N): the bit-fluid serving matmul.

    Static ``wbits`` routes through the plane-serial kernel (cost ∝ wbits),
    masking container MSBs directly (truncation semantics — serve_linear
    adds the dyadic-rounding requant the models use); use
    core.bitfluid.fluid_int8_matmul for traced (runtime-tensor) bits.
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    x_scale = bf.symmetric_scale(x2, abits)
    x_q = bf.quantize(x2, x_scale, abits)
    acc = bitplane_matmul(x_q, w_q, n_planes=wbits, interpret=interpret)
    y = acc.astype(jnp.float32) * x_scale * jnp.asarray(w_scale, jnp.float32)
    return y.reshape(*lead, -1)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    interpret: bool = False) -> jnp.ndarray:
    """Flat-head flash attention: (BH, Sq, hd). Pads Sq/Sk/hd to tiles.

    Off-TPU, sequences longer than one ref chunk take the blockwise
    online-softmax ref (O(S·chunk) memory — dry-run artifacts keep the
    flash memory posture); short ones take the exact oracle."""
    from repro.kernels.flash_attention import flash_attention as _fa
    interpret = _interp(interpret)
    if not (use_pallas() or interpret):
        if max(q.shape[1], k.shape[1]) > kref.FLASH_CHUNK:
            return kref.flash_attention_chunked_ref(q, k, v, causal=causal,
                                                    window=window)
        return kref.flash_attention_ref(q, k, v, causal, window)
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    bq = min(128, max(8, 1 << (Sq - 1).bit_length())) if Sq < 128 else 128
    bk = min(128, max(8, 1 << (Sk - 1).bit_length())) if Sk < 128 else 128
    qp = _pad_to(q, (1, bq, 128))
    kp = _pad_to(k, (1, bk, 128))
    vp = _pad_to(v, (1, bk, 128))
    out = _fa(qp, kp, vp, causal=causal, window=window, k_len=Sk,
              scale=hd ** -0.5, bq=bq, bk=bk, interpret=interpret)
    return out[:, :Sq, :hd]


def ssm_update(state: jnp.ndarray, layer, x: jnp.ndarray, dt: jnp.ndarray,
               dA: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray, live, *,
               interpret: bool = False):
    """One decode step of a Mamba-2 layer's state, in place at ``layer``
    of the stacked state (L, B, H, P, N) f32: x (B, H, P), dt and
    dA = exp(a dt) (B, H), b and c (B, N), live (B,).  Live rows get
    h = dA h + (dt x) B and y = h . C; empty rows keep their state and
    give y = 0.  Returns (y (B, H, P) f32, state).

    On the TPU the Pallas ``ssm_update`` kernel reads each live slot's
    block once and writes it back into the same buffer; elsewhere the
    jnp oracle (a dynamic-update-slice XLA also does in place)."""
    interpret = _interp(interpret)
    f32 = jnp.float32
    x, dt, dA = x.astype(f32), dt.astype(f32), dA.astype(f32)
    b, c = b.astype(f32), c.astype(f32)
    if not (use_pallas() or interpret):
        return kref.ssm_update_ref(state, layer, x, dt, dA, b, c, live)
    B, H, P = x.shape
    u_t = jnp.swapaxes(dt[..., None] * x, 1, 2)              # (B, P, H)
    a_t = jnp.broadcast_to(dA[:, None, :], (B, P, H))
    y_t, state = _ssm_update_pallas(state, layer, u_t, a_t, b[:, None],
                                    c[:, None], live, interpret=interpret)
    return jnp.swapaxes(y_t, 1, 2), state
