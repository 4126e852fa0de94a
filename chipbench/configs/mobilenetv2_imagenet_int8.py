"""Plain int8 reference of MobileNetV2 1.0 on ImageNet (224x224, 1000 classes).

The network of arXiv:1801.04381, Table 2: a 3x3 stride-2 stem to 32
channels, 17 inverted-residual blocks, a 1x1 head to 1280 channels, global
average pooling and a fully-connected classifier. The first block has an
expansion factor of 1 and, as in every public reference of the network
(torchvision's ``InvertedResidual`` adds the 1x1 expansion only when
t != 1), no expansion: its depthwise reads the stem's output, padded with
that domain's zero point, and its weights carry ``w_exp``, ``b_exp`` and
``m_exp`` as ``None``.

This module stands apart from the program under test: it imports nothing
from ``repro``. It gives the benchmark the same API as its sibling
``mobilenetv2_vww_int8.py`` (``seed_key``, ``make_weights``, ``images``,
``logits``, ``forward(..., bits)``, ``domains``, ``relu6_cap``,
``stage_costs``), and reuses that file's integer helpers, loaded by path:
the TFLite int8 arithmetic, the seeded draws and the calibration are one
code for both networks.
"""

from __future__ import annotations

import functools
import importlib.util
import pathlib

import jax
import numpy as np


def _sibling(name: str):
    path = pathlib.Path(__file__).with_name(f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_v = _sibling("mobilenetv2_vww_int8")

INT8_MIN, INT8_MAX = _v.INT8_MIN, _v.INT8_MAX
N_CALIB = _v.N_CALIB
domains, relu6_cap, block_plan = _v.domains, _v.relu6_cap, _v.block_plan
seed_key, images = _v.seed_key, _v.images


def _expands(cin: int, cmid: int) -> bool:
    """t > 1: the block has a 1x1 expansion (t = 1 has none)."""
    return cmid != cin


# --------------------------------------------------------------------------
# The forward pass
# --------------------------------------------------------------------------


def forward(cfg, weights, images_, bits: int = 8):
    """int8 logits (N, n_classes) of int8 images (N, H, W, C).

    ``bits`` < 8 rounds every weight and every stage output onto a coarser
    grid: the control computed in the precision below the one stated.
    """
    dom = domains(cfg)
    q6 = relu6_cap(dom["relu6"])
    zp6, zpl = dom["relu6"][1], dom["linear"][1]
    lw = functools.partial(_v._to_bits, zp=0, bits=bits)
    act = functools.partial(_v._to_bits, bits=bits)

    st = weights["stem"]
    acc = _v._stem_acc(images_, lw(st["w"]), dom["image"][1]) + st["b"]
    x = act(_v._requant(acc, st["m"], zp6, zp6, q6), zp6)
    x_dom = dom["relu6"]
    plan, _ = block_plan(cfg)
    for (_, (cin, cmid, _, stride), _, residual), bw in zip(
            plan, weights["blocks"]):
        if _expands(cin, cmid):
            acc = _v._dot(x, lw(bw["w_exp"])) + bw["b_exp"]
            f1 = act(_v._requant(acc, bw["m_exp"], zp6, zp6, q6), zp6)
            zp_f1 = zp6
        else:                           # t=1: F1 is the block input
            f1, zp_f1 = x, x_dom[1]
        acc = _v._dw_acc(f1, lw(bw["w_dw"]), zp_f1, stride) + bw["b_dw"]
        f2 = act(_v._requant(acc, bw["m_dw"], zp6, zp6, q6), zp6)
        acc = _v._dot(f2, lw(bw["w_proj"])) + bw["b_proj"]
        y = act(_v._requant(acc, bw["m_proj"], zpl, INT8_MIN, INT8_MAX), zpl)
        if residual:
            y = act(_v._residual(y, x, dom["linear"], x_dom), zpl)
        x, x_dom = y, dom["linear"]
    hd = weights["head"]
    acc = _v._dot(x, lw(hd["w"])) + hd["b"]
    h = act(_v._requant(acc, hd["m"], zp6, zp6, q6), zp6)
    fc = weights["fc"]
    acc = _v._dot(_v._gap(h), lw(fc["w"])) + fc["b"]
    zpo = dom["logits"][1]
    return act(_v._requant(acc, fc["m"], zpo, INT8_MIN, INT8_MAX), zpo)


# --------------------------------------------------------------------------
# Seeded weights (one jitted call on the device)
# --------------------------------------------------------------------------


def _weight_shapes(cfg):
    """(weight shape, output channels) of every stage, in order."""
    c0 = cfg["blocks"][0][1]
    out = [((3, 3, cfg["img_ch"], c0), c0)]
    for _, cin, cmid, cout, _ in cfg["blocks"]:
        if _expands(cin, cmid):
            out.append(((cin, cmid), cmid))
        out += [((3, 3, cmid), cmid), ((cmid, cout), cout)]
    out += [((cfg["blocks"][-1][3], cfg["head_ch"]), cfg["head_ch"]),
            ((cfg["head_ch"], cfg["n_classes"]), cfg["n_classes"])]
    return out


@functools.partial(jax.jit, static_argnums=(0,))
def _make_weights(frozen_cfg, key_data):
    cfg = _v._thaw(frozen_cfg)
    dom = domains(cfg)
    q6 = relu6_cap(dom["relu6"])
    zp6, zpl = dom["relu6"][1], dom["linear"][1]
    t6 = q6 - zp6
    shapes = _weight_shapes(cfg)
    draws = _v._Draws(jax.random.wrap_key_data(key_data),
                      sum(int(np.prod(sh)) for sh, _ in shapes),
                      sum(c for _, c in shapes))
    x = images(cfg, key_data, N_CALIB, stream=0)
    calibrate, int8_weights = _v._calibrate, _v._int8_weights

    c0 = cfg["blocks"][0][1]
    w = int8_weights(draws, (3, 3, cfg["img_ch"], c0))
    zp_img = dom["image"][1]
    acc = _v._stem_acc(x, w, zp_img)
    b, m = calibrate(draws, acc, w, zp_img, (0, 1, 2), t6)
    stem = {"w": w, "b": b, "m": m}
    x = _v._requant(acc + b, m, zp6, zp6, q6)
    x_dom = dom["relu6"]

    blocks = []
    plan, _ = block_plan(cfg)
    for _, (cin, cmid, cout, stride), _, residual in plan:
        bw = {"w_exp": None, "b_exp": None, "m_exp": None}
        if _expands(cin, cmid):
            bw["w_exp"] = int8_weights(draws, (cin, cmid))
            acc = _v._dot(x, bw["w_exp"])
            bw["b_exp"], bw["m_exp"] = calibrate(
                draws, acc, bw["w_exp"], x_dom[1], (0,), t6)
            f1 = _v._requant(acc + bw["b_exp"], bw["m_exp"], zp6, zp6, q6)
            zp_f1 = zp6
        else:
            f1, zp_f1 = x, x_dom[1]
        bw["w_dw"] = int8_weights(draws, (3, 3, cmid))
        acc = _v._dw_acc(f1, bw["w_dw"], zp_f1, stride)
        bw["b_dw"], bw["m_dw"] = calibrate(draws, acc, bw["w_dw"], zp_f1,
                                           (0, 1), t6)
        f2 = _v._requant(acc + bw["b_dw"], bw["m_dw"], zp6, zp6, q6)
        bw["w_proj"] = int8_weights(draws, (cmid, cout))
        acc = _v._dot(f2, bw["w_proj"])
        bw["b_proj"], bw["m_proj"] = calibrate(
            draws, acc, bw["w_proj"], zp6, (0,), _v.LINEAR_TARGET)
        y = _v._requant(acc + bw["b_proj"], bw["m_proj"], zpl, INT8_MIN,
                        INT8_MAX)
        if residual:
            y = _v._residual(y, x, dom["linear"], x_dom)
        blocks.append(bw)
        x, x_dom = y, dom["linear"]

    w = int8_weights(draws, (cfg["blocks"][-1][3], cfg["head_ch"]))
    acc = _v._dot(x, w)
    b, m = calibrate(draws, acc, w, x_dom[1], (0,), t6)
    head = {"w": w, "b": b, "m": m}
    h = _v._requant(acc + b, m, zp6, zp6, q6)
    g = _v._gap(h)
    w = int8_weights(draws, (cfg["head_ch"], cfg["n_classes"]))
    acc = _v._dot(g, w)
    b, m = calibrate(draws, acc, w, zp6, (0,), _v.LINEAR_TARGET)
    return {"stem": stem, "blocks": blocks, "head": head,
            "fc": {"w": w, "b": b, "m": m}}


def make_weights(cfg, key_data):
    """The served weights of ``cfg``, from the seed's key, on the device."""
    return _make_weights(_v._freeze(_v._network_keys(cfg)), key_data)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _forward_jit(frozen_cfg, weights, images_, bits):
    return forward(_v._thaw(frozen_cfg), weights, images_, bits)


def logits(cfg, weights, images_, bits: int = 8, rows: int = 256):
    """Reference (or control) logits on the host, ``rows`` images a call."""
    frozen = _v._freeze(_v._network_keys(cfg))
    out = [np.asarray(_forward_jit(frozen, weights, images_[i:i + rows],
                                   bits))
           for i in range(0, len(images_), rows)]
    return np.concatenate(out)


# --------------------------------------------------------------------------
# Operations and bytes, from the shapes
# --------------------------------------------------------------------------


def stage_costs(cfg):
    """Per stage and image: MACs and the minimum HBM bytes of its maps, and
    per call: its parameter bytes (int8 weights, int32 biases, float32
    multipliers). A block without expansion is of kind ``"dw"`` (it runs
    its own kernel) and counts no expansion; the others are ``"dsc"``."""
    out = _v.stage_costs(cfg)
    plan, _ = block_plan(cfg)
    for i, (name, (ci, cm, co, s), h, _) in enumerate(plan, start=1):
        if _expands(ci, cm):
            continue
        h2 = -(-h // s)
        out[i] = {"name": name, "kind": "dw",
                  "macs": h2 * h2 * 9 * cm + h2 * h2 * cm * co,
                  "map_bytes": h * h * ci + h2 * h2 * co,
                  "param_bytes": 9 * cm + cm * co + 8 * (cm + co)}
    return out
