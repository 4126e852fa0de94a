"""MobileNetV2-class int8 network built from the paper's DSC blocks.

The network embeds the four bottleneck layers the paper benchmarks
(Fig. 14 / Tables III & VI) at the exact feature-map sizes it reports:

    block "3rd"  : 40x40x8,  t=6 -> F1 40x40x48
    block "5th"  : 20x20x16, t=6 -> F1/F2 20x20x96  (38.4 KB buffer, Eq. 2)
    block "8th"  : 10x10x24, t=6 -> F1 10x10x144
    block "15th" : 5x5x56,   t=6 -> F1 5x5x336

plus stride-2 transition blocks, an int8 3x3 stem and a pointwise head —
a VWW-style classifier (the CFU-Playground deployment model). The whole
network runs in TFLite int8 arithmetic end-to-end, under any of the
execution disciplines (v0 reference / v1 pixel-wise / v2 pipelined /
v3 row-tile), which are bit-identical by construction. The Pallas kernel
runs the blocks on the CFU fast path (``cfu.fastpath``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dsc as dsc_mod
from repro.core import quant
from repro.core.dsc import DSCBlockSpec, QuantizedDSCParams
from repro.core.fusion import Schedule, run_block

# (name, cin, cmid, cout, stride) at the paper's feature-map sizes;
# input feature map is 40x40x8 (stem output).
PAPER_BLOCKS: Tuple[Tuple[str, int, int, int, int], ...] = (
    ("3rd", 8, 48, 8, 1),        # 40x40 -> 40x40   (paper Fig. 14 layer 3)
    ("b2", 8, 48, 16, 2),        # 40x40 -> 20x20
    ("5th", 16, 96, 16, 1),      # 20x20 -> 20x20   (paper layer 5)
    ("b4", 16, 96, 24, 2),       # 20x20 -> 10x10
    ("8th", 24, 144, 24, 1),     # 10x10 -> 10x10   (paper layer 8)
    ("b6", 24, 144, 56, 2),      # 10x10 -> 5x5
    ("15th", 56, 336, 56, 1),    # 5x5  -> 5x5      (paper layer 15)
)

PAPER_LAYER_HW: Dict[str, int] = {"3rd": 40, "5th": 20, "8th": 10, "15th": 5}


@dataclasses.dataclass
class MobileNetV2Params:
    """Quantized network: stem + DSC blocks + head + classifier."""

    stem_w: jnp.ndarray          # (3, 3, 3, C0) int8
    stem_b: jnp.ndarray          # int32 (zp-folded)
    stem_m: jnp.ndarray          # f32 per-channel requant
    qp_img: quant.QParams
    qp_stem: quant.QParams
    blocks: List[QuantizedDSCParams]
    head_w: jnp.ndarray          # (C_last, C_head) int8
    head_b: jnp.ndarray
    head_m: jnp.ndarray
    qp_head: quant.QParams
    fc_w: jnp.ndarray            # (C_head, n_classes) int8
    fc_b: jnp.ndarray
    fc_m: jnp.ndarray
    qp_logits: quant.QParams


def block_specs() -> List[Tuple[str, DSCBlockSpec]]:
    return [(name, DSCBlockSpec(cin=ci, cmid=cm, cout=co, stride=s))
            for name, ci, cm, co, s in PAPER_BLOCKS]


def block_input_hw(img_hw: int = 80) -> List[int]:
    """Input feature-map size of each PAPER_BLOCKS entry for an
    ``img_hw`` image (the stride-2 stem halves it first)."""
    hw, out = -(-img_hw // 2), []
    for _, spec in block_specs():
        out.append(hw)
        hw = spec.out_hw(hw, hw)[0]
    return out


def init_and_quantize(key, *, img_hw: int = 80, head_ch: int = 128,
                      n_classes: int = 2) -> MobileNetV2Params:
    """Random float network -> post-training int8 quantization (TFLite
    workflow), calibrated on one random image."""
    rng = np.random.default_rng(np.asarray(jax.random.key_data(key))[-1])
    img = rng.standard_normal((img_hw, img_hw, 3)).astype(np.float32)

    # --- stem: 3x3 s2 standard conv ----------------------------------------
    c0 = PAPER_BLOCKS[0][1]
    stem_w = rng.standard_normal((3, 3, 3, c0)).astype(np.float32) * 0.3
    stem_b = np.zeros(c0, np.float32)
    x = _conv2d_f32(img, stem_w, stride=2) + stem_b
    x = np.clip(x, 0, 6)
    qp_img = quant.choose_qparams(img)
    qp_stem = quant.choose_qparams(x)
    qpw = quant.choose_qparams(stem_w, channel_axis=3)
    stem_wq = np.asarray(quant.quantize(stem_w, qpw, channel_axis=3))
    stem_bq = (np.round(stem_b / (np.float32(qp_img.scale) * qpw.scale_arr()))
               .astype(np.int64)
               + quant.fold_zero_point_correction(stem_wq, qp_img.zero_point,
                                                  (0, 1, 2)))
    stem_m = quant.effective_scale(qp_img.scale, qpw.scale, qp_stem.scale)

    # --- DSC blocks ----------------------------------------------------------
    blocks: List[QuantizedDSCParams] = []
    for i, (name, spec) in enumerate(block_specs()):
        p32 = dsc_mod.init_dsc_block_f32(jax.random.fold_in(key, i), spec)
        qp = dsc_mod.quantize_dsc_block(p32, spec, x)
        blocks.append(qp)
        x = np.asarray(dsc_mod.dsc_block_f32(jnp.asarray(x), p32, spec))

    # --- head 1x1 + GAP + fc -------------------------------------------------
    c_last = PAPER_BLOCKS[-1][3]
    head_w = rng.standard_normal((c_last, head_ch)).astype(np.float32) * 0.1
    h = np.clip(np.einsum("hwc,cm->hwm", x, head_w), 0, 6)
    qp_in_head = blocks[-1].qp_out
    qp_head = quant.choose_qparams(h)
    qpw_h = quant.choose_qparams(head_w, channel_axis=1)
    head_wq = np.asarray(quant.quantize(head_w, qpw_h, channel_axis=1))
    head_bq = quant.fold_zero_point_correction(head_wq, qp_in_head.zero_point,
                                               (0,))
    head_m = quant.effective_scale(qp_in_head.scale, qpw_h.scale,
                                   qp_head.scale)
    g = h.mean(axis=(0, 1))
    fc_w = rng.standard_normal((head_ch, n_classes)).astype(np.float32) * 0.1
    logits = g @ fc_w
    qp_logits = quant.choose_qparams(logits)
    qpw_fc = quant.choose_qparams(fc_w, channel_axis=1)
    fc_wq = np.asarray(quant.quantize(fc_w, qpw_fc, channel_axis=1))
    fc_bq = quant.fold_zero_point_correction(fc_wq, qp_head.zero_point, (0,))
    fc_m = quant.effective_scale(qp_head.scale, qpw_fc.scale, qp_logits.scale)

    return MobileNetV2Params(
        stem_w=jnp.asarray(stem_wq), stem_b=jnp.asarray(stem_bq, jnp.int32),
        stem_m=jnp.asarray(stem_m), qp_img=qp_img, qp_stem=qp_stem,
        blocks=blocks,
        head_w=jnp.asarray(head_wq), head_b=jnp.asarray(head_bq, jnp.int32),
        head_m=jnp.asarray(head_m), qp_head=qp_head,
        fc_w=jnp.asarray(fc_wq), fc_b=jnp.asarray(fc_bq, jnp.int32),
        fc_m=jnp.asarray(fc_m), qp_logits=qp_logits)


def _conv2d_f32(x, w, stride=1):
    """SAME 3x3 conv, float (calibration only). x: (H, W, Cin)."""
    return np.asarray(jax.lax.conv_general_dilated(
        jnp.asarray(x)[None], jnp.asarray(w),
        window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))[0])


def _stem_int8(img_q, p: MobileNetV2Params):
    """int8 3x3 s2 conv: zero-point padding (pad_top = pad_left = 1, the
    convention of ``core.dsc._window_indices`` and the CFU's LD_WIN gather)
    + zp-folded bias on raw int8 taps + requant + ReLU6."""
    img_p = jnp.pad(img_q, ((1, 1), (1, 1), (0, 0)),
                    constant_values=p.qp_img.zero_point)
    acc = jax.lax.conv_general_dilated(
        img_p.astype(jnp.int32)[None],
        p.stem_w.astype(jnp.int32),
        window_strides=(2, 2), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))[0]
    # stem_b carries the -zp_img * sum(w) fold, so raw-int8 taps with
    # zp_img padding are exact (pad taps contribute zero, see dsc.py NOTE).
    acc = acc + p.stem_b
    return quant.requantize(acc, p.stem_m, p.qp_stem.zero_point, relu=True,
                            relu6_max_q=quant.relu6_max_q(p.qp_stem))


def forward_int8(img, p: MobileNetV2Params,
                 schedule: Schedule = Schedule.V3_INTRA_STAGE,
                 return_quantized: bool = False):
    """Full int8 inference for one image (H, W, 3) float32 -> logits.

    ``return_quantized`` returns the raw int8 logits instead of their
    dequantized floats — the exact words a hardware CFU would hand back,
    and what the CFU simulator's differential tests compare against.
    """
    img_q = quant.quantize(img, p.qp_img)
    x = _stem_int8(img_q, p)

    for qp in p.blocks:
        x = run_block(x, qp, schedule)

    # head 1x1 + ReLU6
    acc = jnp.einsum("hwc,cm->hwm", x.astype(jnp.int32),
                     p.head_w.astype(jnp.int32)) + p.head_b
    h = quant.requantize(acc, p.head_m, p.qp_head.zero_point, relu=True,
                         relu6_max_q=quant.relu6_max_q(p.qp_head))
    # global average pool (int32 mean, rounded)
    hw = h.shape[0] * h.shape[1]
    g = jnp.round(h.astype(jnp.int32).sum(axis=(0, 1)) / hw).astype(jnp.int32)
    g = jnp.clip(g, -128, 127).astype(jnp.int8)
    # fc
    acc = (g.astype(jnp.int32) @ p.fc_w.astype(jnp.int32)) + p.fc_b
    logits_q = quant.requantize(acc, p.fc_m, p.qp_logits.zero_point)
    if return_quantized:
        return logits_q
    return quant.dequantize(logits_q, p.qp_logits)


def forward_batch(imgs, p: MobileNetV2Params, **kw):
    return jax.vmap(lambda im: forward_int8(im, p, **kw))(imgs)
