"""The paper's core technique: fused pixel-wise dataflow for DSC blocks.

A MobileNetV2 inverted-residual block is the three-stage sandwich

    Expansion (1x1 conv, C -> M) -> Depthwise (3x3, per-channel, stride s)
                                 -> Projection (1x1 conv, M -> N) [-> +residual]

A block whose expansion factor is 1 (``cmid == cin``, MobileNetV2's first
bottleneck) has no expansion, as in every public reference of the network
(torchvision's ``InvertedResidual`` adds the 1x1 only when t != 1): its
depthwise reads the block input, so F1 is the input, in the input's domain.

This module implements the block in three execution disciplines:

* ``dsc_block_reference``      -- layer-by-layer (the paper's v0 baseline):
      the intermediate feature maps F1 (H1 x W1 x M) and F2 (H2 x W2 x M)
      are fully materialized, and padding is applied *explicitly* by
      allocating a padded F1 (paper Fig. 13a).
* ``dsc_block_fused_pixelwise`` -- the paper's v1 dataflow: one output pixel
      is computed to completion across all three stages; F1 exists only as a
      3x3xM register tile and F2 as a length-M vector. Out-of-bounds window
      reads return the quantization zero-point ("on-the-fly padding",
      Fig. 13b). Expansion work overlapping between neighbouring windows is
      recomputed -- the paper's No-Local-Reuse trade (recompute < data
      movement).
* ``dsc_block_fused_rowtile``   -- the TPU-adapted schedule (DESIGN.md §2):
      same zero-buffer property but at row-tile granularity, so the
      expansion halo is computed once per tile instead of once per pixel
      (recompute factor (t+2)/t per row instead of 9x). This is the
      granularity the Pallas kernel (kernels/fused_dsc.py) uses.

All three produce BIT-IDENTICAL int8 outputs (integer accumulation is
associative; requantization is applied elementwise with the same constants),
which tests/test_dsc.py asserts exactly, not with allclose.

Tensor layout is HWC (single image) / NHWC (batched via vmap). Weights:
    w_exp  : (C, M)      int8, per-output-channel scale
    w_dw   : (3, 3, M)   int8, per-channel scale
    w_proj : (M, N)      int8, per-output-channel scale
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.quant import QParams

# ---------------------------------------------------------------------------
# Block specification & parameters
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DSCBlockSpec:
    """Static shape/arity description of one inverted-residual block."""

    cin: int
    cmid: int          # = cin * expansion_factor
    cout: int
    stride: int = 1
    kernel: int = 3    # depthwise kernel (paper: 3x3)

    def __post_init__(self):
        if self.cmid < self.cin:
            raise ValueError(f"cmid={self.cmid} < cin={self.cin}: an "
                             "inverted residual expands (t >= 1)")

    @property
    def has_expansion(self) -> bool:
        """t > 1; a t=1 block's depthwise reads its input directly."""
        return self.cmid != self.cin

    @property
    def has_residual(self) -> bool:
        return self.stride == 1 and self.cin == self.cout

    def out_hw(self, h: int, w: int) -> Tuple[int, int]:
        # SAME padding semantics (TFLite): ceil division by stride.
        return (-(-h // self.stride), -(-w // self.stride))

    def macs(self, h: int, w: int) -> Dict[str, int]:
        """Layer-by-layer MAC counts (the paper's Section II formulas)."""
        h2, w2 = self.out_hw(h, w)
        return {
            "expansion": (h * w * self.cin * self.cmid
                          if self.has_expansion else 0),
            "depthwise": h2 * w2 * self.kernel * self.kernel * self.cmid,
            "projection": h2 * w2 * self.cmid * self.cout,
        }


@dataclasses.dataclass
class QuantizedDSCParams:
    """All tensors + quantization constants for one int8 block.

    Biases are int32 and *include* the zero-point correction term
    (-zp_in * sum_k w) so the MAC loops stream raw int8 activations,
    exactly as the paper's engines do (quant.fold_zero_point_correction).

    A block without expansion (``spec.has_expansion`` false) carries
    ``w_exp``, ``b_exp`` and ``m_exp`` as ``None``; its F1 is the input, so
    ``b_dw`` folds ``qp_in``'s zero point and ``qp_f1``/``q6_f1`` are not
    read.
    """

    spec: DSCBlockSpec
    # int8 weights
    w_exp: jnp.ndarray
    w_dw: jnp.ndarray
    w_proj: jnp.ndarray
    # int32 biases (zero-point-folded)
    b_exp: jnp.ndarray
    b_dw: jnp.ndarray
    b_proj: jnp.ndarray
    # activation qparams (per-tensor)
    qp_in: QParams
    qp_f1: QParams
    qp_f2: QParams
    qp_out: QParams
    # requant multipliers (float32 effective scales, per-channel)
    m_exp: jnp.ndarray
    m_dw: jnp.ndarray
    m_proj: jnp.ndarray
    # quantized ReLU6 clamp value in F1/F2 domains
    q6_f1: int = 127
    q6_f2: int = 127
    # residual-add rescale constants (TFLite ADD), see residual_add_q
    qp_res_out: Optional[QParams] = None


def init_dsc_block_f32(key, spec: DSCBlockSpec) -> Dict[str, jnp.ndarray]:
    """He-initialized float32 weights for one block (training/calibration);
    ``w_exp``/``b_exp`` are ``None`` for a block without expansion."""
    k1, k2, k3 = jax.random.split(key, 3)
    w_exp = b_exp = None
    if spec.has_expansion:
        w_exp = jax.random.normal(k1, (spec.cin, spec.cmid), jnp.float32)
        w_exp = w_exp * np.sqrt(2.0 / spec.cin)
        b_exp = jnp.zeros((spec.cmid,))
    w_dw = jax.random.normal(k2, (spec.kernel, spec.kernel, spec.cmid))
    w_dw = w_dw * np.sqrt(2.0 / (spec.kernel * spec.kernel))
    w_proj = jax.random.normal(k3, (spec.cmid, spec.cout), jnp.float32)
    w_proj = w_proj * np.sqrt(2.0 / spec.cmid)
    zeros = jnp.zeros
    return {
        "w_exp": w_exp, "b_exp": b_exp,
        "w_dw": w_dw, "b_dw": zeros((spec.cmid,)),
        "w_proj": w_proj, "b_proj": zeros((spec.cout,)),
    }


def dsc_block_f32(x, p: Dict[str, jnp.ndarray], spec: DSCBlockSpec):
    """Float reference semantics (HWC). Used to calibrate the int8 path."""
    f1 = x
    if spec.has_expansion:
        f1 = jnp.einsum("hwc,cm->hwm", x, p["w_exp"]) + p["b_exp"]
        f1 = jnp.clip(f1, 0.0, 6.0)  # ReLU6
    f1p = jnp.pad(f1, ((1, 1), (1, 1), (0, 0)))
    s, k = spec.stride, spec.kernel
    h2, w2 = spec.out_hw(x.shape[0], x.shape[1])
    acc = jnp.zeros((h2, w2, spec.cmid), jnp.float32)
    for dy in range(k):
        for dx in range(k):
            win = jax.lax.slice(
                f1p, (dy, dx, 0),
                (dy + (h2 - 1) * s + 1, dx + (w2 - 1) * s + 1, spec.cmid),
                (s, s, 1))
            acc = acc + win * p["w_dw"][dy, dx]
    f2 = jnp.clip(acc + p["b_dw"], 0.0, 6.0)
    y = jnp.einsum("hwm,mn->hwn", f2, p["w_proj"]) + p["b_proj"]  # linear
    if spec.has_residual:
        y = y + x
    return y


def quantize_dsc_block(params_f32: Dict[str, jnp.ndarray],
                       spec: DSCBlockSpec,
                       calib_x: np.ndarray) -> QuantizedDSCParams:
    """Post-training quantization of a float block, TFLite-style.

    ``calib_x`` is a float activation sample (H, W, C) used to pick
    activation ranges (the TinyML workflow the paper describes: train in
    float, quantize for deployment).
    """
    p = {k: np.asarray(v) for k, v in params_f32.items()}
    # --- activation ranges from a float forward pass -----------------------
    x = np.asarray(calib_x, np.float32)
    expand = spec.has_expansion
    f1 = (np.clip(np.einsum("hwc,cm->hwm", x, p["w_exp"]) + p["b_exp"],
                  0, 6) if expand else x)
    f1p = np.pad(f1, ((1, 1), (1, 1), (0, 0)))
    s, k = spec.stride, spec.kernel
    h2, w2 = spec.out_hw(x.shape[0], x.shape[1])
    acc = np.zeros((h2, w2, spec.cmid), np.float32)
    for dy in range(k):
        for dx in range(k):
            acc += (f1p[dy:dy + (h2 - 1) * s + 1:s,
                        dx:dx + (w2 - 1) * s + 1:s] * p["w_dw"][dy, dx])
    f2 = np.clip(acc + p["b_dw"], 0, 6)
    y = np.einsum("hwm,mn->hwn", f2, p["w_proj"]) + p["b_proj"]

    qp_in = quant.choose_qparams(x)
    # ReLU6 output: range ~[0, 6]; without expansion F1 is the input
    qp_f1 = quant.choose_qparams(f1) if expand else qp_in
    qp_f2 = quant.choose_qparams(f2)
    qp_out = quant.choose_qparams(y)

    # --- weights: per-output-channel symmetric -----------------------------
    qp_wdw = quant.choose_qparams(p["w_dw"], channel_axis=2)
    qp_wproj = quant.choose_qparams(p["w_proj"], channel_axis=1)
    w_dw_q = np.asarray(quant.quantize(p["w_dw"], qp_wdw, channel_axis=2))
    w_proj_q = np.asarray(quant.quantize(p["w_proj"], qp_wproj, channel_axis=1))

    # --- int32 biases with zero-point folding ------------------------------
    def qbias(b, s_in, s_w):
        return np.round(b / (np.asarray(s_in) * np.asarray(s_w))).astype(np.int64)

    w_exp_q = b_exp = m_exp = None
    if expand:
        qp_wexp = quant.choose_qparams(p["w_exp"], channel_axis=1)
        w_exp_q = jnp.asarray(
            quant.quantize(p["w_exp"], qp_wexp, channel_axis=1))
        b_exp = jnp.asarray(
            qbias(p["b_exp"], qp_in.scale, qp_wexp.scale)
            + quant.fold_zero_point_correction(np.asarray(w_exp_q),
                                               qp_in.zero_point, (0,)),
            jnp.int32)
        m_exp = jnp.asarray(quant.effective_scale(qp_in.scale,
                                                  qp_wexp.scale,
                                                  qp_f1.scale))
    b_dw = (qbias(p["b_dw"], qp_f1.scale, qp_wdw.scale)
            + quant.fold_zero_point_correction(w_dw_q, qp_f1.zero_point, (0, 1)))
    b_proj = (qbias(p["b_proj"], qp_f2.scale, qp_wproj.scale)
              + quant.fold_zero_point_correction(w_proj_q, qp_f2.zero_point, (0,)))

    m_dw = quant.effective_scale(qp_f1.scale, qp_wdw.scale, qp_f2.scale)
    m_proj = quant.effective_scale(qp_f2.scale, qp_wproj.scale, qp_out.scale)

    def q6(qp: QParams) -> int:
        return int(min(127, qp.zero_point + round(6.0 / float(np.asarray(qp.scale)))))

    return QuantizedDSCParams(
        spec=spec,
        w_exp=w_exp_q, w_dw=jnp.asarray(w_dw_q),
        w_proj=jnp.asarray(w_proj_q),
        b_exp=b_exp, b_dw=jnp.asarray(b_dw, jnp.int32),
        b_proj=jnp.asarray(b_proj, jnp.int32),
        qp_in=qp_in, qp_f1=qp_f1, qp_f2=qp_f2, qp_out=qp_out,
        m_exp=m_exp, m_dw=jnp.asarray(m_dw),
        m_proj=jnp.asarray(m_proj),
        q6_f1=q6(qp_f1), q6_f2=q6(qp_f2),
    )


# ---------------------------------------------------------------------------
# Shared int8 stage arithmetic (identical ops in every execution discipline,
# so the disciplines are bit-identical by construction).
# ---------------------------------------------------------------------------


def _expansion_acc(x_q, p: QuantizedDSCParams):
    """Raw int8 activations -> int32 accumulator (+folded bias)."""
    acc = jnp.einsum("...c,cm->...m", x_q.astype(jnp.int32),
                     p.w_exp.astype(jnp.int32))
    return acc + p.b_exp


def f1_zero_point(p: QuantizedDSCParams) -> int:
    """Zero point of F1: the expansion's output domain, or the input's for
    a block without expansion (its depthwise pads with the input's zp)."""
    return (p.qp_f1.zero_point if p.spec.has_expansion
            else p.qp_in.zero_point)


def _expansion_f1(x_q, p: QuantizedDSCParams):
    """int8 input pixels -> int8 F1 pixels (the input itself at t=1)."""
    if not p.spec.has_expansion:
        return jnp.asarray(x_q, jnp.int8)
    return quant.requantize(_expansion_acc(x_q, p), p.m_exp,
                            p.qp_f1.zero_point, relu=True,
                            relu6_max_q=p.q6_f1)


def _depthwise_acc_from_tile(f1_tile, w_dw, b_dw):
    """(..., 3, 3, M) int8 tile -> (..., M) int32 accumulator."""
    prod = f1_tile.astype(jnp.int32) * w_dw.astype(jnp.int32)
    return prod.sum(axis=(-3, -2)) + b_dw


def _projection_acc(f2_q, p: QuantizedDSCParams):
    acc = jnp.einsum("...m,mn->...n", f2_q.astype(jnp.int32),
                     p.w_proj.astype(jnp.int32))
    return acc + p.b_proj


def residual_add_q(y_q, x_q, p: QuantizedDSCParams):
    """TFLite quantized ADD: rescale both operands into the output domain."""
    s_y = float(np.asarray(p.qp_out.scale))
    s_x = float(np.asarray(p.qp_in.scale))
    # Output of the add reuses qp_out's scale (calibrated on y + x would be
    # more exact; for a framework demo the sum range is bounded by 2*max).
    acc = (s_y * (y_q.astype(jnp.float32) - p.qp_out.zero_point)
           + s_x * (x_q.astype(jnp.float32) - p.qp_in.zero_point))
    out = jnp.round(acc / s_y) + p.qp_out.zero_point
    return jnp.clip(out, quant.INT8_MIN, quant.INT8_MAX).astype(jnp.int8)


# ---------------------------------------------------------------------------
# v0: layer-by-layer reference (explicit padding, full F1/F2 materialized)
# ---------------------------------------------------------------------------


def dsc_block_reference(x_q, p: QuantizedDSCParams):
    """The paper's baseline: each stage completes over the whole feature map.

    F1 and F2 are materialized at full size; padding is an explicit
    allocation (Fig. 13a). This is both the oracle for tests and the
    "traffic baseline" for benchmarks.
    """
    spec = p.spec
    # Stage 1: Expansion over the entire map (F1 is the input at t=1).
    f1_q = _expansion_f1(x_q, p)
    # Explicit padded intermediate (what the fused dataflow eliminates).
    f1_pad = jnp.pad(f1_q, ((1, 1), (1, 1), (0, 0)),
                     constant_values=f1_zero_point(p))
    s, k = spec.stride, spec.kernel
    h2, w2 = spec.out_hw(x_q.shape[0], x_q.shape[1])
    acc = jnp.zeros((h2, w2, spec.cmid), jnp.int32)
    for dy in range(k):
        for dx in range(k):
            win = jax.lax.slice(
                f1_pad, (dy, dx, 0),
                (dy + (h2 - 1) * s + 1, dx + (w2 - 1) * s + 1, spec.cmid),
                (s, s, 1))
            acc = acc + win.astype(jnp.int32) * p.w_dw[dy, dx].astype(jnp.int32)
    # NOTE: zero-point folding makes padding-with-zp equivalent to the
    # explicit (f1 - zp) * w formulation: sum((f1-zp)w) = sum(f1*w) - zp*sum(w).
    f2_q = quant.requantize(acc + p.b_dw, p.m_dw, p.qp_f2.zero_point,
                            relu=True, relu6_max_q=p.q6_f2)
    y_q = quant.requantize(_projection_acc(f2_q, p), p.m_proj,
                           p.qp_out.zero_point, relu=False)
    if spec.has_residual:
        y_q = residual_add_q(y_q, x_q, p)
    return y_q


# ---------------------------------------------------------------------------
# v1: fused pixel-wise dataflow (the paper's contribution)
# ---------------------------------------------------------------------------


def _window_indices(h2: int, w2: int, stride: int, k: int):
    """Input coordinates of the kxk window for every output pixel.

    SAME padding: window top-left = out*stride - pad with pad = (k-1)//2 for
    odd k (TFLite SAME for stride 1; for stride 2 TFLite pads asymmetrically
    -- we match jnp.pad(1,1) used by the reference, i.e. pad_top=1).
    """
    oy, ox = jnp.meshgrid(jnp.arange(h2), jnp.arange(w2), indexing="ij")
    dy, dx = jnp.meshgrid(jnp.arange(k), jnp.arange(k), indexing="ij")
    iy = oy[..., None, None] * stride + dy - 1
    ix = ox[..., None, None] * stride + dx - 1
    return iy, ix  # (h2, w2, k, k)


def gather_window_otf(x_q, iy, ix, zero_point: int):
    """On-the-fly padding (Fig. 13b): out-of-bounds reads return the
    zero-point value instead of reading a materialized padded tensor."""
    x_q = jnp.asarray(x_q)
    h, w = x_q.shape[0], x_q.shape[1]
    valid = (iy >= 0) & (iy < h) & (ix >= 0) & (ix < w)
    win = x_q[jnp.clip(iy, 0, h - 1), jnp.clip(ix, 0, w - 1)]
    return jnp.where(valid[..., None], win,
                     jnp.asarray(zero_point, x_q.dtype))


def dsc_block_fused_pixelwise(x_q, p: QuantizedDSCParams):
    """Paper v1: one output pixel to completion; F1 = 3x3xM registers,
    F2 = length-M register vector. lax.scan is the 'pixel loop'; the scan
    carry holds NO feature-map state -- that is the zero-buffer property.
    """
    spec = p.spec
    h2, w2 = spec.out_hw(x_q.shape[0], x_q.shape[1])
    iy, ix = _window_indices(h2, w2, spec.stride, spec.kernel)
    flat_iy = iy.reshape(h2 * w2, spec.kernel, spec.kernel)
    flat_ix = ix.reshape(h2 * w2, spec.kernel, spec.kernel)

    def one_pixel(_, idx):
        wy, wx = flat_iy[idx], flat_ix[idx]
        # --- Expansion stage: 3x3xC window -> 3x3xM F1 tile (registers) ----
        win = gather_window_otf(x_q, wy, wx, p.qp_in.zero_point)
        f1_tile = _expansion_f1(win, p)
        # The *expansion*'s own input window needs on-the-fly padding too:
        # positions whose source pixel was padding must yield F1 = zp_f1
        # after the depthwise sees them. Since expansion(zp_in-pad pixel)
        # != zp_f1 in general, mask in the F1 domain (the hardware's address
        # check happens before the expansion engines are fed).
        h, w = x_q.shape[0], x_q.shape[1]
        valid = (wy >= 0) & (wy < h) & (wx >= 0) & (wx < w)
        f1_tile = jnp.where(valid[..., None], f1_tile,
                            jnp.asarray(f1_zero_point(p), jnp.int8))
        # --- Depthwise stage: 3x3xM tile -> M-vector F2 (registers) --------
        acc = _depthwise_acc_from_tile(f1_tile, p.w_dw, p.b_dw)
        f2_vec = quant.requantize(acc, p.m_dw, p.qp_f2.zero_point,
                                  relu=True, relu6_max_q=p.q6_f2)
        # --- Projection stage: M-vector -> N-vector output pixel -----------
        y = quant.requantize(_projection_acc(f2_vec, p), p.m_proj,
                             p.qp_out.zero_point, relu=False)
        return None, y

    _, ys = jax.lax.scan(one_pixel, None, jnp.arange(h2 * w2))
    y_q = ys.reshape(h2, w2, spec.cout)
    if spec.has_residual:
        y_q = residual_add_q(y_q, x_q, p)
    return y_q


# ---------------------------------------------------------------------------
# v3-style: fused row-tile dataflow (TPU adaptation; halo recompute only)
# ---------------------------------------------------------------------------


def dsc_block_fused_rowtile(x_q, p: QuantizedDSCParams, tile_rows: int = 4):
    """Zero-buffer fusion at row-tile granularity.

    For each tile of ``tile_rows`` output rows, the expansion stage computes
    the (tile_rows*stride + 2)-row haloed F1 strip once; depthwise and
    projection then consume it entirely in registers/VMEM. Bit-identical to
    the pixel-wise dataflow, but the expansion recompute factor drops from
    ~9x to (t*s+2)/(t*s) per tile -- the VMEM-capacity advantage TPU has over
    the paper's register-only pipeline (DESIGN.md §2).
    """
    spec = p.spec
    h, w = x_q.shape[0], x_q.shape[1]
    h2, w2 = spec.out_hw(h, w)
    s, k = spec.stride, spec.kernel
    n_tiles = -(-h2 // tile_rows)
    # Pad the *input* rows so every tile's halo gather is static-shaped.
    in_rows_per_tile = (tile_rows - 1) * s + k  # rows of x needed per tile

    def one_tile(_, t):
        row0 = t * tile_rows            # first output row of this tile
        in_row0 = row0 * s - 1          # first input row incl. halo
        # --- Expansion over the haloed strip (computed ONCE per tile) ------
        rows = in_row0 + jnp.arange(in_rows_per_tile)
        cols = jnp.arange(-1, w + 1)    # full-width halo
        valid_r = (rows >= 0) & (rows < h)
        valid_c = (cols >= 0) & (cols < w)
        strip = x_q[jnp.clip(rows, 0, h - 1)[:, None],
                    jnp.clip(cols, 0, w - 1)[None, :]]
        valid = valid_r[:, None] & valid_c[None, :]
        f1 = _expansion_f1(strip, p)
        f1 = jnp.where(valid[..., None], f1,
                       jnp.asarray(f1_zero_point(p), jnp.int8))
        # --- Depthwise over the strip (VMEM-resident, never stored) --------
        acc = jnp.zeros((tile_rows, w2, spec.cmid), jnp.int32)
        for dy in range(k):
            for dx in range(k):
                winv = jax.lax.slice(
                    f1, (dy, dx, 0),
                    (dy + (tile_rows - 1) * s + 1,
                     dx + (w2 - 1) * s + 1, spec.cmid), (s, s, 1))
                acc = acc + winv.astype(jnp.int32) * p.w_dw[dy, dx].astype(jnp.int32)
        f2 = quant.requantize(acc + p.b_dw, p.m_dw, p.qp_f2.zero_point,
                              relu=True, relu6_max_q=p.q6_f2)
        # --- Projection (output-stationary accumulate) ---------------------
        y = quant.requantize(_projection_acc(f2, p), p.m_proj,
                             p.qp_out.zero_point, relu=False)
        return None, y

    _, tiles = jax.lax.scan(one_tile, None, jnp.arange(n_tiles))
    y_q = tiles.reshape(n_tiles * tile_rows, w2, spec.cout)[:h2]
    if spec.has_residual:
        y_q = residual_add_q(y_q, x_q, p)
    return y_q
