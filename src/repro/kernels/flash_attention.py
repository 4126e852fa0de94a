"""Pallas TPU kernel: flash attention — the paper's zero-buffer dataflow
applied to attention.

The (T, T) score matrix S = QK^T is attention's "intermediate feature map":
layer-by-layer execution materializes S (and P = softmax(S)) in HBM, which is
exactly the paper's F1/F2 memory wall at O(T^2) scale. This kernel computes
one query tile to completion across all K/V tiles with an online softmax, so
S/P exist only as VMEM tiles for one grid step — the same zero-buffer
property as the fused DSC kernel, with

    Expansion  stage ~ S_tile = Q_tile @ K_tile^T      (MXU)
    Mix        stage ~ online softmax rescale          (VPU, the "depthwise"
                                                        structural slot)
    Projection stage ~ acc += P_tile @ V_tile          (output-stationary,
                                                        VMEM accumulator)

Grid = (batch*heads, q tiles, k tiles); the k axis is sequential
("arbitrary") so the accumulator + running max/denominator revolve in VMEM
scratch, and Pallas double-buffers the K/V tile DMAs against compute (the
paper's v2/v3 pipelining, done by the compiler).

Supports: causal masking, local (sliding-window) masking, logit soft-capping
(gemma2), all selected statically so masked k-tiles are skipped entirely
(block sparsity, not just masking).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, block_q: int, block_k: int, seq_k: int, causal: bool,
                  window: Optional[int], softcap: Optional[float],
                  sm_scale: float, n_kblocks: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                        # (block_q, d)
    k = k_ref[0]                        # (block_k, d)
    v = v_ref[0]                        # (block_k, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    # --- masking (the attention analogue of on-the-fly padding: invalid
    # positions are substituted in-register, never materialized) ------------
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = k_pos < seq_k                         # ragged tail
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    # --- online softmax (running max / denominator in VMEM scratch) --------
    m_prev = m_ref[...]                          # (block_q, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                       # (block_q, block_k)
    alpha = jnp.exp(m_prev - m_new)              # rescale factor
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == n_kblocks - 1)
    def _store():
        # Guard fully-masked rows (e.g. causal row 0 with window 0 overlap).
        denom = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """Zero-buffer attention.

    Args:
      q: (BH, Tq, d) — batch*heads leading. k/v: (BH, Tk, d). GQA callers
        repeat/reshape kv to match BH before the call (ops.mha handles it).
      causal: causal mask. window: sliding-window size (None = global).
      softcap: logit soft-capping constant (gemma2-style).
    Returns: (BH, Tq, d), same dtype as q.
    """
    bh, tq, d = q.shape
    tk = k.shape[1]
    sm_scale = float(sm_scale if sm_scale is not None else d ** -0.5)
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    if tq % block_q:
        block_q = next(b for b in range(block_q, 0, -1) if tq % b == 0)
    kpad = (-tk) % block_k
    if kpad:  # pad K/V; the in-kernel seq_k mask ignores the tail
        k = jnp.pad(k, ((0, 0), (0, kpad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, kpad), (0, 0)))
    n_kblocks = k.shape[1] // block_k
    grid = (bh, tq // block_q, n_kblocks)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, seq_k=tk,
        causal=causal, window=window, softcap=softcap, sm_scale=sm_scale,
        n_kblocks=n_kblocks)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denominator
            pltpu.VMEM((block_q, d), jnp.float32),   # output-stationary acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
