"""Pure-jnp oracles for the float Pallas kernels in this package.

Each function is the semantic ground truth its kernel is swept against in
tests/test_kernels.py (shape/dtype sweeps, assert_allclose). The int8 DSC
kernel's oracle is ``core.dsc.dsc_block_reference``, compared EXACTLY.
No pallas imports here — these run on any backend and define what the
kernels mean.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# fused_ffn oracle
# ---------------------------------------------------------------------------

_ACTS = {
    "silu": lambda x: x * jax.nn.sigmoid(x),
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "relu_sq": lambda x: jnp.square(jnp.maximum(x, 0.0)),
    "relu": lambda x: jnp.maximum(x, 0.0),
}


def fused_ffn_ref(x, w_gate, w_up, w_down, *, act: str = "silu"):
    """y = act(x @ w_gate) * (x @ w_up) @ w_down, f32 accumulation."""
    f = _ACTS[act]
    x32 = x.astype(jnp.float32)
    if w_gate is None:
        h = f(x32 @ w_up.astype(jnp.float32))
    else:
        h = (f(x32 @ w_gate.astype(jnp.float32))
             * (x32 @ w_up.astype(jnp.float32)))
    return (h.astype(x.dtype).astype(jnp.float32)
            @ w_down.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# flash_attention oracle — materializes the full (Tq, Tk) score matrix
# (exactly what the kernel refuses to do).
# ---------------------------------------------------------------------------


def attention_ref(q, k, v, *, causal: bool = True,
                  window: Optional[int] = None,
                  softcap: Optional[float] = None,
                  sm_scale: Optional[float] = None):
    """(BH, Tq, d) x (BH, Tk, d) -> (BH, Tq, d)."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    scale = float(sm_scale if sm_scale is not None else d ** -0.5)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = jnp.arange(tq)[:, None]
    k_pos = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # Rows with no valid key (possible with extreme windows) -> zeros.
    any_valid = mask.any(axis=-1, keepdims=True)
    p = jnp.where(any_valid, p, 0.0)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)
