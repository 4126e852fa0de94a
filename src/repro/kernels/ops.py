"""jit'd public entry points for the Pallas kernels — the "custom
instructions" of the JAX world (the analogue of the paper's CFU R-type
interface: one call per fused block).

On the CPU the kernels run with interpret=True (Pallas executes the kernel
body in Python); on a TPU they are compiled by Mosaic (interpret=False).
The default resolves via ``default_interpret()``. Model code calls these
wrappers, never the kernels directly.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.dsc import QuantizedDSCParams, residual_add_q
from repro.kernels import fused_dsc as _dsc
from repro.kernels import fused_ffn as _ffn
from repro.kernels import flash_attention as _fa


def default_interpret() -> bool:
    """True when no TPU is present (CPU container -> interpreter mode)."""
    return jax.default_backend() != "tpu"


# --- fused DSC block -------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("stride", "zps", "q6",
                                             "tile_rows", "interpret"))
def dsc_block(x_q, w_exp, w_dw9, w_proj, b_exp, b_dw, b_proj,
              m_exp, m_dw, m_proj, *, stride: int, zps, q6,
              tile_rows: int = 4, interpret: Optional[bool] = None):
    """One fused Ex->Dw->Pr inverted-residual block (no residual add)."""
    interp = default_interpret() if interpret is None else interpret
    return _dsc.fused_dsc_pallas(
        x_q, w_exp, w_dw9, w_proj, b_exp, b_dw, b_proj, m_exp, m_dw, m_proj,
        stride=stride, zps=zps, q6=q6, tile_rows=tile_rows, interpret=interp)


@functools.partial(jax.jit, static_argnames=("stride", "zps", "q6",
                                             "tile_rows", "interpret"))
def dw_block(x_q, w_dw9, w_proj, b_dw, b_proj, m_dw, m_proj, *,
             stride: int, zps, q6: int, tile_rows: int = 4,
             interpret: Optional[bool] = None):
    """One fused Dw->Pr block without expansion (t=1; no residual add).

    Its own entry, so the device trace names its kernel ``jit_dw_block``
    apart from the expanding blocks' ``jit_dsc_block``. ``zps`` is
    (zp_in, zp_f2, zp_out), ``q6`` the F2 ReLU6 cap."""
    interp = default_interpret() if interpret is None else interpret
    return _dsc.dw_pallas(
        x_q, w_dw9, w_proj, b_dw, b_proj, m_dw, m_proj, stride=stride,
        zps=zps, q6=q6, tile_rows=tile_rows, interpret=interp)


def dsc_block_q(x_q, p: QuantizedDSCParams, *, tile_rows: int = 4):
    """One int8 inverted-residual block through its kernel: ``dsc_block``,
    or ``dw_block`` for a block without expansion, then the residual add
    where the block has one.

    Not jitted itself, so the device trace keeps naming the kernels
    ``jit_dsc_block`` and ``jit_dw_block``."""
    spec = p.spec
    w_dw9 = p.w_dw.reshape(9, spec.cmid)
    zp_in, zp_f2, zp_out = (p.qp_in.zero_point, p.qp_f2.zero_point,
                            p.qp_out.zero_point)
    if spec.has_expansion:
        y = dsc_block(
            x_q, p.w_exp, w_dw9, p.w_proj, p.b_exp, p.b_dw, p.b_proj,
            p.m_exp, p.m_dw, p.m_proj, stride=spec.stride,
            zps=(zp_in, p.qp_f1.zero_point, zp_f2, zp_out),
            q6=(p.q6_f1, p.q6_f2), tile_rows=tile_rows)
    else:
        y = dw_block(
            x_q, w_dw9, p.w_proj, p.b_dw, p.b_proj, p.m_dw, p.m_proj,
            stride=spec.stride, zps=(zp_in, zp_f2, zp_out), q6=p.q6_f2,
            tile_rows=tile_rows)
    if spec.has_residual:
        y = residual_add_q(y, x_q, p)
    return y


# --- fused FFN -------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("act", "block_t", "block_f",
                                             "interpret"))
def ffn(x, w_gate, w_up, w_down, *, act: str = "silu", block_t: int = 256,
        block_f: int = 512, interpret: Optional[bool] = None):
    """Fused gated/ungated FFN on a (T, d) token tile."""
    interp = default_interpret() if interpret is None else interpret
    return _ffn.fused_ffn_pallas(x, w_gate, w_up, w_down, act=act,
                                 block_t=block_t, block_f=block_f,
                                 interpret=interp)


# --- flash attention -------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "sm_scale", "block_q", "block_k",
                                             "interpret"))
def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              softcap: Optional[float] = None,
              sm_scale: Optional[float] = None, block_q: int = 128,
              block_k: int = 128, interpret: Optional[bool] = None):
    """Flash attention on (BH, Tq, d) tensors."""
    interp = default_interpret() if interpret is None else interpret
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, sm_scale=sm_scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interp)


def mha(q, k, v, *, n_kv_heads: int, causal: bool = True,
        window: Optional[int] = None, softcap: Optional[float] = None,
        sm_scale: Optional[float] = None, interpret: Optional[bool] = None):
    """Multi-head GQA wrapper: (B, T, H, d) q, (B, T, Hkv, d) k/v.

    Repeats KV heads to match query heads, flattens (B, H) -> BH, and calls
    the flash kernel.
    """
    b, tq, h, d = q.shape
    group = h // n_kv_heads
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, -1, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, -1, d)
    o = attention(qf, kf, vf, causal=causal, window=window, softcap=softcap,
                  sm_scale=sm_scale, interpret=interpret)
    return o.reshape(b, h, tq, d).transpose(0, 2, 1, 3)
