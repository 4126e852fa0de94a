"""Pallas TPU kernel: fused int8 Expansion -> Depthwise -> Projection.

This is the paper's accelerator re-targeted at the TPU memory hierarchy
(DESIGN.md §2). One ``pl.pallas_call`` computes an entire inverted-residual
block; the grid iterates over *output row tiles* and, per tile:

    1. reads the haloed input rows straight from the VMEM-resident map
       (on-the-fly padding: out-of-bounds rows yield the F1 zero-point and
       the column halo is a zero-point border of the F1 strip — the paper's
       Fig. 13b address-check logic, realised as masked selects),
    2. Expansion: int8 x int8 -> int32 matmul on the MXU, requantize, ReLU6
       (the paper's nine 8-way-MAC engines -> one MXU matmul per row),
    3. Depthwise: nine shifted multiply-adds on the VPU over the VMEM-
       resident F1 strip (the paper's 9-way MAC array, No-Local-Reuse);
       stride 2 decimates with strided reads of that strip,
    4. Projection: int8 matmul + requantize, output-stationary in VMEM
       (the paper's 56 OS accumulator engines -> one MXU matmul per row).

The intermediate feature maps F1/F2 exist ONLY inside this kernel's VMEM
(a per-tile scratch strip and registers) for the lifetime of one grid step —
they are never written to HBM. That is the zero-buffer property; XLA's
layer-by-layer lowering of the reference implementation materializes both
(benchmarks/bench_traffic.py shows the byte difference).

Mosaic (the TPU kernel compiler) constrains the formulation: the input map
is indexed as a ref (a loaded value cannot be dynamically sliced), every
matmul works on one (W, C) row (int8 reshapes of narrow maps such as
(6, 10, 24) -> (60, 24) have no vector layout), and the depthwise taps are
read back from the F1 scratch with ``pl.ds`` strides (a strided slice of a
value is refused for stride 2).

Granularity note (hardware adaptation): the paper computes one output PIXEL
per pipeline beat because its F1 storage is a 3x3xM register file. VMEM is
~16 MiB, so we fuse at row-tile granularity instead — same zero-buffer
property, but the expansion halo is computed once per tile rather than once
per pixel (recompute factor (s*t+2)/(s*t) instead of 9x). Grid steps are
pipelined by Pallas (DMA double-buffering), which plays the role of the
paper's v2/v3 inter/intra-stage pipelining.

Weight layout: w_dw is passed as (9, M) — tap-major, exactly the paper's
nine-bank depthwise filter buffer (Fig. 12: bank i holds tap i of every
filter, so one "row" feeds all MACs of tap i in one go).

A block without expansion (t=1, MobileNetV2's first bottleneck) runs the
same body with step 2 left out (``dw_pallas``, a static flag): the F1
strip is filled from the input rows themselves, and the halo carries the
input's zero point.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT8_MIN, INT8_MAX = -128, 127
LANES = 128


def _requant(acc_i32, m, zp_out: int, lo: int, hi: int):
    """int32 accumulator -> int8, float-multiplier requantization.

    Identical arithmetic to core.quant.requantize so kernel output is
    bit-identical to the pure-JAX disciplines.
    """
    y = jnp.round(acc_i32.astype(jnp.float32) * m)
    y = y.astype(jnp.int32) + zp_out
    return jnp.clip(y, lo, hi).astype(jnp.int8)


def _matmul_i32(a_i8, w_i8):
    return jax.lax.dot_general(a_i8, w_i8, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _fused_dsc_kernel(
    *refs, h: int, w: int,
    stride: int, tile_rows: int,
    zp_f1: int, zp_f2: int, zp_out: int,
    q6_f1: int, q6_f2: int, expand: bool = True,
):
    if expand:
        (x_ref, w_exp_ref, w_dw_ref, w_proj_ref, b_exp_ref, b_dw_ref,
         b_proj_ref, m_exp_ref, m_dw_ref, m_proj_ref, out_ref, f1_ref) = refs
    else:
        (x_ref, w_dw_ref, w_proj_ref, b_dw_ref, b_proj_ref, m_dw_ref,
         m_proj_ref, out_ref, f1_ref) = refs
    t = pl.program_id(0)
    s, k = stride, 3
    w2 = -(-w // s)
    n_chunks, in_rows = f1_ref.shape[0], f1_ref.shape[1]
    r0 = t * tile_rows * s - 1  # first input row incl. top halo (may be -1)

    # ---- column halo: zero-point border of the F1 strip (Fig. 13b) ---------
    zcol = jnp.full((n_chunks, in_rows, 1, LANES), zp_f1, jnp.int32)
    f1_ref[:, :, 0:1, :] = zcol
    f1_ref[:, :, w + 1:w + 2, :] = zcol

    # ---- Expansion stage: MXU int8 matmul + requant + ReLU6, per row -------
    if expand:
        w_exp = w_exp_ref[...]
        b_exp, m_exp = b_exp_ref[...], m_exp_ref[...]
    for i in range(in_rows):           # unrolled: in_rows is small & static
        r = r0 + i
        row = x_ref[jnp.clip(r, 0, h - 1)]                       # (W, C)
        if not expand:
            # t=1: F1 is the input row itself, in the input's domain. Its
            # lanes past C are left as they are: their depthwise weights
            # are the zero padding, so they add nothing.
            f1 = jnp.where(jnp.logical_and(r >= 0, r < h),
                           row.astype(jnp.int32), zp_f1)
            for c in range(n_chunks):
                width = min(LANES, f1.shape[1] - c * LANES)
                f1_ref[c, i, 1:w + 1, 0:width] = \
                    f1[:, c * LANES:c * LANES + width]
            continue
        f1 = _requant(_matmul_i32(row, w_exp) + b_exp, m_exp,
                      zp_f1, zp_f1, q6_f1)
        f1 = jnp.where(jnp.logical_and(r >= 0, r < h),
                       f1.astype(jnp.int32), zp_f1)
        for c in range(n_chunks):
            f1_ref[c, i, 1:w + 1, :] = f1[:, c * LANES:(c + 1) * LANES]

    # ---- Depthwise (VPU, nine taps) + Projection (MXU), per output row -----
    w_dw = w_dw_ref[...].astype(jnp.int32)                    # (9, Mp)
    b_dw, m_dw = b_dw_ref[...], m_dw_ref[...]
    w_proj = w_proj_ref[...]
    b_proj, m_proj = b_proj_ref[...], m_proj_ref[...]
    for oy in range(tile_rows):
        chunks = []
        for c in range(n_chunks):
            acc2 = jnp.zeros((w2, LANES), jnp.int32)
            for dy in range(k):
                for dx in range(k):
                    cols = pl.ds(dx, w2, stride=s) if s > 1 else \
                        pl.ds(dx, w2)
                    tap = f1_ref[c, oy * s + dy, cols, :]
                    wt = w_dw[dy * k + dx:dy * k + dx + 1,
                              c * LANES:(c + 1) * LANES]
                    acc2 = acc2 + tap * wt
            chunks.append(acc2)
        acc2 = chunks[0] if n_chunks == 1 else jnp.concatenate(chunks, 1)
        f2 = _requant(acc2 + b_dw, m_dw, zp_f2, zp_f2, q6_f2)
        acc3 = _matmul_i32(f2, w_proj) + b_proj
        out_ref[oy] = _requant(acc3, m_proj, zp_out, INT8_MIN, INT8_MAX)


def fused_dsc_pallas(
    x_q, w_exp, w_dw9, w_proj, b_exp, b_dw, b_proj, m_exp, m_dw, m_proj,
    *, stride: int, zps: Tuple[int, int, int, int],
    q6: Tuple[int, int], tile_rows: int = 4, interpret: bool = False,
):
    """Launch the fused DSC kernel.

    Args:
      x_q: (H, W, C) int8 input feature map.
      w_exp: (C, M) int8. w_dw9: (9, M) int8, tap-major. w_proj: (M, N) int8.
      b_*: int32 biases (zero-point folded). m_*: float32 requant multipliers.
      zps: (zp_in, zp_f1, zp_f2, zp_out). q6: quantized ReLU6 caps (f1, f2).
        zp_in is not read: out-of-map rows and columns are filled in the F1
        domain, where the reference pads.
      tile_rows: output rows computed per grid step (VMEM working-set knob).
    Returns: (H2, W2, N) int8.
    """
    return _launch(x_q, (w_exp, b_exp, m_exp), w_dw9, w_proj, b_dw, b_proj,
                   m_dw, m_proj, stride=stride, zp_f1=zps[1], zp_f2=zps[2],
                   zp_out=zps[3], q6=q6, tile_rows=tile_rows,
                   interpret=interpret)


def dw_pallas(
    x_q, w_dw9, w_proj, b_dw, b_proj, m_dw, m_proj,
    *, stride: int, zps: Tuple[int, int, int], q6: int,
    tile_rows: int = 4, interpret: bool = False,
):
    """Launch the kernel for a block without expansion (t=1, C == M).

    The same body with the expansion left out: F1 is the input map, and
    out-of-map rows and columns are filled with the input's zero point.
    ``zps`` is (zp_in, zp_f2, zp_out) and ``q6`` the F2 ReLU6 cap; the
    other arguments are as for :func:`fused_dsc_pallas`.
    """
    return _launch(x_q, None, w_dw9, w_proj, b_dw, b_proj, m_dw, m_proj,
                   stride=stride, zp_f1=zps[0], zp_f2=zps[1], zp_out=zps[2],
                   q6=(INT8_MAX, q6), tile_rows=tile_rows,
                   interpret=interpret)


def _launch(x_q, exp, w_dw9, w_proj, b_dw, b_proj, m_dw, m_proj, *,
            stride: int, zp_f1: int, zp_f2: int, zp_out: int,
            q6: Tuple[int, int], tile_rows: int, interpret: bool):
    """One ``pallas_call`` of the block; ``exp`` is (w_exp, b_exp, m_exp),
    or None for a block whose F1 is its input."""
    h, w, cin = x_q.shape
    cmid = w_dw9.shape[1]
    cout = w_proj.shape[1]
    h2, w2 = -(-h // stride), -(-w // stride)
    # Keep the requested tile granularity even when it doesn't divide h2:
    # run ceil(h2/tile_rows) grid steps over a row-padded output and slice
    # the valid rows off afterwards. The kernel clips + masks out-of-range
    # input rows, so the overhang tile computes discardable rows instead of
    # reading out of bounds.
    tile_rows = min(tile_rows, h2)
    n_tiles = -(-h2 // tile_rows)
    h2p = n_tiles * tile_rows
    in_rows = (tile_rows - 1) * stride + 3

    # Mosaic's strided loads want a buffer whose last dim is exactly one
    # 128-lane vreg row, so the F1 strip is kept as whole 128-channel
    # chunks. The expanded channels are zero-padded up to that width: a
    # padded channel has zero weights, bias and multiplier, so its F1/F2
    # values are the zero points and its projection weights are zero —
    # the block's output is unchanged.
    n_chunks = -(-cmid // LANES)
    pad = n_chunks * LANES - cmid
    expand = exp is not None
    w_exp, b_exp, m_exp = exp if expand else (None, None, None)
    if expand:
        w_exp = jnp.pad(w_exp, ((0, 0), (0, pad)))
    w_dw9 = jnp.pad(w_dw9, ((0, 0), (0, pad)))
    w_proj = jnp.pad(w_proj, ((0, pad), (0, 0)))
    # per-channel vectors as (1, n) rows, broadcast over a (W, n) row
    if expand:
        vecs = [jnp.pad(v, (0, pad)).reshape(1, -1)
                for v in (b_exp, b_dw, m_exp, m_dw)]
        b_exp, b_dw, m_exp, m_dw = vecs
    else:
        b_dw, m_dw = [jnp.pad(v, (0, pad)).reshape(1, -1)
                      for v in (b_dw, m_dw)]
    b_proj, m_proj = b_proj.reshape(1, -1), m_proj.reshape(1, -1)
    cmid_p = n_chunks * LANES

    kernel = functools.partial(
        _fused_dsc_kernel, h=h, w=w, stride=stride, tile_rows=tile_rows,
        zp_f1=zp_f1, zp_f2=zp_f2, zp_out=zp_out,
        q6_f1=q6[0], q6_f2=q6[1], expand=expand)

    whole = lambda shape: pl.BlockSpec(shape, lambda t: (0,) * len(shape))
    operands = [
        (x_q, whole((h, w, cin))),        # x: whole map stays in VMEM
        (w_exp, whole((cin, cmid_p))),    # w_exp (broadcast, like Fig. 11)
        (w_dw9, whole((9, cmid_p))),      # w_dw nine-bank layout (Fig. 12)
        (w_proj, whole((cmid_p, cout))),  # w_proj (per-engine LUTRAM, Fig. 8)
        (b_exp, whole((1, cmid_p))), (b_dw, whole((1, cmid_p))),
        (b_proj, whole((1, cout))),
        (m_exp, whole((1, cmid_p))), (m_dw, whole((1, cmid_p))),
        (m_proj, whole((1, cout))),
    ]
    operands = [(a, spec) for a, spec in operands if a is not None]
    y = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[spec for _, spec in operands],
        out_specs=pl.BlockSpec((tile_rows, w2, cout), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((h2p, w2, cout), jnp.int8),
        # the haloed F1 strip of one tile (int32: the depthwise operand)
        scratch_shapes=[pltpu.VMEM((n_chunks, in_rows, w + 2, LANES),
                                   jnp.int32)],
        interpret=interpret,
    )(*[a for a, _ in operands])
    return y if h2p == h2 else y[:h2]
