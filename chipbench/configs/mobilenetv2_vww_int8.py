"""Plain int8 reference of the MobileNetV2 Visual Wake Words network.

This module stands apart from the program under test: it imports nothing
from ``repro`` and takes nothing the program made. It gives the benchmark

* ``make_weights``: the served weights, made on the device from the seed in
  one jitted call: int8 weights, int32 zero-point-folded biases and float32
  per-channel requantization multipliers. The multipliers and biases are
  calibrated on seeded images so that every stage fills its int8 domain.
  The activation domains (scale, zero point) are fixed by the configuration
  file, so every seed serves the same compiled program;
* ``forward``: the int8 inference the program has to reproduce bit for bit.
  TFLite int8 arithmetic: int32 accumulation, float32 requantization
  rounded half to even, zero-point padding, ReLU6 as a clamp, a TFLite ADD
  for residuals, a rounded integer global average, a linear classifier;
* ``forward(..., bits=4)``: the same network with weights and activations
  rounded to 4 bits, the lower-precision control the check must reject;
* ``stage_costs``: the algorithm's operations and minimum HBM bytes per
  stage, from the shapes alone.

The network (arXiv:2511.21232 Fig. 14 and Tables III and VI; MobileNetV2
inverted residuals, arXiv:1801.04381): a 3x3 stride-2 stem conv with ReLU6,
then DSC blocks (1x1 expansion + ReLU6, 3x3 depthwise + ReLU6, linear 1x1
projection, residual add where stride is 1 and widths match), a 1x1 head
with ReLU6, global average pooling and a fully-connected classifier.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

INT8_MIN, INT8_MAX = -128, 127
N_CALIB = 16           # seeded images the multipliers are calibrated on
LINEAR_TARGET = 100    # |acc| max of a linear stage maps to this many steps
BIAS_SHARE = 8         # random bias term spans +-(|acc| max / BIAS_SHARE)


# --------------------------------------------------------------------------
# Domains fixed by the configuration
# --------------------------------------------------------------------------


def domains(cfg):
    """(scale, zero point) of every activation tensor, by role."""
    return {k: (float(v[0]), int(v[1])) for k, v in cfg["quant"].items()}


def relu6_cap(dom) -> int:
    """The quantized value of 6.0 in a domain, at most 127."""
    scale, zp = dom
    return int(min(INT8_MAX, zp + round(6.0 / scale)))


def block_plan(cfg):
    """Per block: name, (cin, cmid, cout, stride), input size, residual."""
    hw = -(-cfg["img_hw"] // 2)
    out = []
    for name, cin, cmid, cout, stride in cfg["blocks"]:
        out.append((name, (cin, cmid, cout, stride), hw,
                    stride == 1 and cin == cout))
        hw = -(-hw // stride)
    return out, hw


# --------------------------------------------------------------------------
# Integer stage arithmetic (batched NHWC)
# --------------------------------------------------------------------------


def _dot(a, w):
    """int8 (..., K) x int8 (K, M) -> exact int32."""
    return jax.lax.dot_general(a, w, (((a.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _taps(x, zp, stride):
    """The nine 3x3 SAME taps of x (N, H, W, C), padded with ``zp``."""
    _, h, w, _ = x.shape
    h2, w2 = -(-h // stride), -(-w // stride)
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)), constant_values=zp)
    return [xp[:, dy:dy + (h2 - 1) * stride + 1:stride,
               dx:dx + (w2 - 1) * stride + 1:stride, :]
            for dy in range(3) for dx in range(3)]


def _requant(acc, m, zp, lo, hi):
    y = jnp.round(acc.astype(jnp.float32) * m).astype(jnp.int32) + zp
    return jnp.clip(y, lo, hi).astype(jnp.int8)


def _stem_acc(x, w, zp):
    cin = x.shape[-1]
    patches = jnp.concatenate(_taps(x, zp, 2), axis=-1)
    return _dot(patches, w.reshape(9 * cin, -1))


def _dw_acc(f1, w_dw, zp, stride):
    acc = 0
    for t, tap in enumerate(_taps(f1, zp, stride)):
        acc = acc + tap.astype(jnp.int32) * w_dw[t // 3, t % 3].astype(
            jnp.int32)
    return acc


def _residual(y, x, dom_y, dom_x):
    """TFLite ADD into the output domain (float32, rounded half to even)."""
    (s_y, zp_y), (s_x, zp_x) = dom_y, dom_x
    acc = (s_y * (y.astype(jnp.float32) - zp_y)
           + s_x * (x.astype(jnp.float32) - zp_x))
    out = jnp.round(acc / s_y) + zp_y
    return jnp.clip(out, INT8_MIN, INT8_MAX).astype(jnp.int8)


def _div_round_half_even(a, n: int):
    """Exact integer a / n rounded half to even (n > 0)."""
    q = jnp.floor_divide(a, n)
    r = a - q * n
    up = (2 * r > n) | ((2 * r == n) & (q % 2 == 1))
    return q + up.astype(a.dtype)


def _gap(h):
    n = h.shape[1] * h.shape[2]
    g = _div_round_half_even(h.astype(jnp.int32).sum(axis=(1, 2)), n)
    return jnp.clip(g, INT8_MIN, INT8_MAX).astype(jnp.int8)


def _to_bits(a, zp, bits):
    """Round an int8 tensor of zero point ``zp`` onto a ``bits``-bit grid."""
    if bits >= 8:
        return a
    step = 1 << (8 - bits)
    v = jnp.round((a.astype(jnp.float32) - zp) / step) * step + zp
    return jnp.clip(v, INT8_MIN, INT8_MAX).astype(jnp.int8)


# --------------------------------------------------------------------------
# The forward pass
# --------------------------------------------------------------------------


def forward(cfg, weights, images, bits: int = 8):
    """int8 logits (N, n_classes) of int8 images (N, H, W, C).

    ``bits`` < 8 rounds every weight and every stage output onto a coarser
    grid: the control computed in the precision below the one stated.
    """
    dom = domains(cfg)
    q6 = relu6_cap(dom["relu6"])
    zp6, zpl = dom["relu6"][1], dom["linear"][1]
    lw = functools.partial(_to_bits, zp=0, bits=bits)
    act = functools.partial(_to_bits, bits=bits)

    st = weights["stem"]
    acc = _stem_acc(images, lw(st["w"]), dom["image"][1]) + st["b"]
    x = act(_requant(acc, st["m"], zp6, zp6, q6), zp6)
    x_dom = dom["relu6"]
    plan, _ = block_plan(cfg)
    for (_, (_, _, _, stride), _, residual), bw in zip(plan,
                                                        weights["blocks"]):
        acc = _dot(x, lw(bw["w_exp"])) + bw["b_exp"]
        f1 = act(_requant(acc, bw["m_exp"], zp6, zp6, q6), zp6)
        acc = _dw_acc(f1, lw(bw["w_dw"]), zp6, stride) + bw["b_dw"]
        f2 = act(_requant(acc, bw["m_dw"], zp6, zp6, q6), zp6)
        acc = _dot(f2, lw(bw["w_proj"])) + bw["b_proj"]
        y = act(_requant(acc, bw["m_proj"], zpl, INT8_MIN, INT8_MAX), zpl)
        if residual:
            y = act(_residual(y, x, dom["linear"], x_dom), zpl)
        x, x_dom = y, dom["linear"]
    hd = weights["head"]
    acc = _dot(x, lw(hd["w"])) + hd["b"]
    h = act(_requant(acc, hd["m"], zp6, zp6, q6), zp6)
    fc = weights["fc"]
    acc = _dot(_gap(h), lw(fc["w"])) + fc["b"]
    zpo = dom["logits"][1]
    return act(_requant(acc, fc["m"], zpo, INT8_MIN, INT8_MAX), zpo)


# --------------------------------------------------------------------------
# Seeded inputs and weights (each one jitted call on the device)
# --------------------------------------------------------------------------


def seed_key(seed: int):
    """Key data for any seed below 2**64 (both 32-bit halves are used)."""
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _images(shape, key_data, stream: int):
    key = jax.random.fold_in(jax.random.wrap_key_data(key_data), stream)
    return jax.random.randint(key, shape, INT8_MIN, INT8_MAX + 1, jnp.int8)


def images(cfg, key_data, n: int, stream: int = 1):
    """``n`` uniform int8 images on the device, from the seed's key."""
    shape = (n, cfg["img_hw"], cfg["img_hw"], cfg["img_ch"])
    return _images(shape, key_data, stream)


class _Draws:
    """All the seed's random numbers, drawn in two calls and handed out in
    order (one draw per tensor would make the program far longer)."""

    def __init__(self, key, n_normal: int, n_uniform: int):
        k1, k2 = jax.random.split(key)
        self._normal = jax.random.normal(k1, (n_normal,), jnp.float32)
        self._uniform = jax.random.uniform(k2, (n_uniform,), jnp.float32,
                                           -1.0, 1.0)
        self._i = self._j = 0

    def normal(self, shape):
        n = int(np.prod(shape))
        out = self._normal[self._i:self._i + n].reshape(shape)
        self._i += n
        return out

    def uniform(self, n: int):
        out = self._uniform[self._j:self._j + n]
        self._j += n
        return out


def _weight_shapes(cfg):
    """(weight shape, output channels) of every stage, in order."""
    c0 = cfg["blocks"][0][1]
    out = [((3, 3, cfg["img_ch"], c0), c0)]
    for _, cin, cmid, cout, _ in cfg["blocks"]:
        out += [((cin, cmid), cmid), ((3, 3, cmid), cmid),
                ((cmid, cout), cout)]
    out += [((cfg["blocks"][-1][3], cfg["head_ch"]), cfg["head_ch"]),
            ((cfg["head_ch"], cfg["n_classes"]), cfg["n_classes"])]
    return out


def _int8_weights(draws, shape):
    """Normal weights quantized per output channel (the last axis),
    symmetric, to int8."""
    z = draws.normal(shape)
    axes = tuple(range(len(shape) - 1))
    amax = jnp.maximum(jnp.abs(z).max(axis=axes, keepdims=True), 1e-6)
    return jnp.round(z / amax * INT8_MAX).astype(jnp.int8)


def _calibrate(draws, acc_raw, w, zp_in, k_axes, target):
    """Bias (zero-point fold + a seeded term) and multiplier of a stage.

    ``acc_raw`` is the raw int8 accumulator over the calibration images;
    the multiplier maps each channel's |acc| maximum to ``target`` steps.
    """
    fold = -zp_in * w.astype(jnp.int32).sum(axis=k_axes)
    red = tuple(range(acc_raw.ndim - 1))
    amax = jnp.abs(acc_raw + fold).max(axis=red)
    beta = jnp.round(draws.uniform(amax.shape[0]) * amax / BIAS_SHARE)
    b = fold + beta.astype(jnp.int32)
    amax = jnp.maximum(jnp.abs(acc_raw + b).max(axis=red), 1)
    m = (target / amax.astype(jnp.float32)).astype(jnp.float32)
    return b, m


@functools.partial(jax.jit, static_argnums=(0,))
def _make_weights(frozen_cfg, key_data):
    cfg = _thaw(frozen_cfg)
    dom = domains(cfg)
    q6 = relu6_cap(dom["relu6"])
    zp6, zpl = dom["relu6"][1], dom["linear"][1]
    t6 = q6 - zp6
    shapes = _weight_shapes(cfg)
    draws = _Draws(jax.random.wrap_key_data(key_data),
                   sum(int(np.prod(sh)) for sh, _ in shapes),
                   sum(c for _, c in shapes))
    x = images(cfg, key_data, N_CALIB, stream=0)

    c0 = cfg["blocks"][0][1]
    w = _int8_weights(draws, (3, 3, cfg["img_ch"], c0))
    zp_img = dom["image"][1]
    acc = _stem_acc(x, w, zp_img)
    b, m = _calibrate(draws, acc, w, zp_img, (0, 1, 2), t6)
    stem = {"w": w, "b": b, "m": m}
    x = _requant(acc + b, m, zp6, zp6, q6)
    x_dom = dom["relu6"]

    blocks = []
    plan, _ = block_plan(cfg)
    for _, (cin, cmid, cout, stride), _, residual in plan:
        bw = {}
        bw["w_exp"] = _int8_weights(draws, (cin, cmid))
        acc = _dot(x, bw["w_exp"])
        bw["b_exp"], bw["m_exp"] = _calibrate(draws, acc, bw["w_exp"],
                                              x_dom[1], (0,), t6)
        f1 = _requant(acc + bw["b_exp"], bw["m_exp"], zp6, zp6, q6)
        bw["w_dw"] = _int8_weights(draws, (3, 3, cmid))
        acc = _dw_acc(f1, bw["w_dw"], zp6, stride)
        bw["b_dw"], bw["m_dw"] = _calibrate(draws, acc, bw["w_dw"],
                                            zp6, (0, 1), t6)
        f2 = _requant(acc + bw["b_dw"], bw["m_dw"], zp6, zp6, q6)
        bw["w_proj"] = _int8_weights(draws, (cmid, cout))
        acc = _dot(f2, bw["w_proj"])
        bw["b_proj"], bw["m_proj"] = _calibrate(
            draws, acc, bw["w_proj"], zp6, (0,), LINEAR_TARGET)
        y = _requant(acc + bw["b_proj"], bw["m_proj"], zpl, INT8_MIN,
                     INT8_MAX)
        if residual:
            y = _residual(y, x, dom["linear"], x_dom)
        blocks.append(bw)
        x, x_dom = y, dom["linear"]

    w = _int8_weights(draws, (cfg["blocks"][-1][3], cfg["head_ch"]))
    acc = _dot(x, w)
    b, m = _calibrate(draws, acc, w, x_dom[1], (0,), t6)
    head = {"w": w, "b": b, "m": m}
    h = _requant(acc + b, m, zp6, zp6, q6)
    g = _gap(h)
    w = _int8_weights(draws, (cfg["head_ch"], cfg["n_classes"]))
    acc = _dot(g, w)
    b, m = _calibrate(draws, acc, w, zp6, (0,), LINEAR_TARGET)
    return {"stem": stem, "blocks": blocks, "head": head,
            "fc": {"w": w, "b": b, "m": m}}


def _freeze(obj):
    if isinstance(obj, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in obj.items()))
    if isinstance(obj, list):
        return ("__list__",) + tuple(_freeze(v) for v in obj)
    return obj


def _thaw(obj):
    if isinstance(obj, tuple) and obj and obj[0] == "__list__":
        return [_thaw(v) for v in obj[1:]]
    if isinstance(obj, tuple):
        return {k: _thaw(v) for k, v in obj}
    return obj


def make_weights(cfg, key_data):
    """The served weights of ``cfg``, from the seed's key, on the device."""
    return _make_weights(_freeze(_network_keys(cfg)), key_data)


def _network_keys(cfg):
    keys = ("img_hw", "img_ch", "head_ch", "n_classes", "blocks", "quant")
    return {k: cfg[k] for k in keys}


@functools.partial(jax.jit, static_argnums=(0, 3))
def _forward_jit(frozen_cfg, weights, images_, bits):
    return forward(_thaw(frozen_cfg), weights, images_, bits)


def logits(cfg, weights, images_, bits: int = 8, rows: int = 256):
    """Reference (or control) logits on the host, ``rows`` images a call."""
    frozen = _freeze(_network_keys(cfg))
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
    multipliers). Algorithmic counts: no lane padding, no halo recompute."""
    hw = cfg["img_hw"]
    h0 = -(-hw // 2)
    c0 = cfg["blocks"][0][1]
    cin = cfg["img_ch"]
    out = [{"name": "stem", "kind": "stem",
            "macs": h0 * h0 * 9 * cin * c0,
            "map_bytes": hw * hw * cin + h0 * h0 * c0,
            "param_bytes": 9 * cin * c0 + 8 * c0}]
    plan, hw_last = block_plan(cfg)
    for name, (ci, cm, co, s), h, _ in plan:
        h2 = -(-h // s)
        out.append({"name": name, "kind": "dsc",
                    "macs": h * h * ci * cm + h2 * h2 * 9 * cm
                    + h2 * h2 * cm * co,
                    "map_bytes": h * h * ci + h2 * h2 * co,
                    "param_bytes": ci * cm + 9 * cm + cm * co
                    + 8 * (2 * cm + co)})
    c_last, hc, nc = cfg["blocks"][-1][3], cfg["head_ch"], cfg["n_classes"]
    out.append({"name": "head", "kind": "head",
                "macs": hw_last * hw_last * c_last * hc,
                "map_bytes": hw_last * hw_last * (c_last + hc),
                "param_bytes": c_last * hc + 8 * hc})
    out.append({"name": "gapfc", "kind": "gapfc", "macs": hc * nc,
                "map_bytes": hw_last * hw_last * hc + nc,
                "param_bytes": hc * nc + 8 * nc})
    return out
