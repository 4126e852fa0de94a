"""Pallas TPU kernel: fused gated FFN (the paper's dataflow on LM blocks).

Computes  y = (act(x @ W_gate) * (x @ W_up)) @ W_down  for one token tile
without ever materializing the (tokens, d_ff) intermediates in HBM.

Stage mapping onto the paper's engines (DESIGN.md §3):

    Expansion  : x @ W_gate[:, j-chunk], x @ W_up[:, j-chunk]
                 (input-stationary — the x tile is held in VMEM across the
                  whole d_ff loop, like the 3x3 IFMAP tile held across the
                  M filter loop in Fig. 6a)
    Mix        : act(h_gate) * h_up   (elementwise — the depthwise stage's
                  structural slot; VPU work between the two MXU matmuls)
    Projection : acc += h @ W_down[j-chunk, :]
                 (output-stationary — `acc` lives in a VMEM scratch
                  accumulator across the d_ff grid loop, exactly the
                  paper's 56 OS accumulators in Fig. 8)

Grid = (token tiles, d_ff chunks); the d_ff axis is the sequential
("arbitrary") axis so the accumulator revolves; Pallas double-buffers the
weight-chunk DMAs against compute, which is the v2/v3 pipelining of the
paper realised by the compiler.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ACTS = {
    "silu": lambda x: x * jax.nn.sigmoid(x),
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "relu_sq": lambda x: jnp.square(jnp.maximum(x, 0.0)),
    "relu": lambda x: jnp.maximum(x, 0.0),
}


def _fused_ffn_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref,
                      *, act: str, n_chunks: int, gated: bool):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    # Expansion (+ mix): chunk of the d_ff intermediate, VMEM-only.
    if gated:
        h = _ACTS[act](jnp.dot(x, wg_ref[...],
                               preferred_element_type=jnp.float32))
        h = h * jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    else:
        h = _ACTS[act](jnp.dot(x, wu_ref[...],
                               preferred_element_type=jnp.float32))
    # Projection: output-stationary accumulate.
    acc_ref[...] += jnp.dot(h.astype(x.dtype), wd_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(j == n_chunks - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def fused_ffn_pallas(x, w_gate, w_up, w_down, *, act: str = "silu",
                     block_t: int = 256, block_f: int = 512,
                     interpret: bool = False):
    """y = act(x@w_gate) * (x@w_up) @ w_down, d_ff never in HBM.

    Args:
      x: (T, d_model). w_gate/w_up: (d_model, d_ff) (w_gate may be None for
      ungated FFNs). w_down: (d_ff, d_model).
    """
    t, d = x.shape
    d_ff = w_up.shape[1]
    gated = w_gate is not None
    block_t = min(block_t, t)
    block_f = min(block_f, d_ff)
    if t % block_t:
        block_t = next(b for b in range(block_t, 0, -1) if t % b == 0)
    if d_ff % block_f:
        block_f = next(b for b in range(block_f, 0, -1) if d_ff % b == 0)
    n_chunks = d_ff // block_f
    grid = (t // block_t, n_chunks)

    kernel = functools.partial(_fused_ffn_kernel, act=act,
                               n_chunks=n_chunks, gated=gated)
    in_specs = [
        pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),       # x tile (IS)
        pl.BlockSpec((d, block_f), lambda i, j: (0, j)),       # W_gate chunk
        pl.BlockSpec((d, block_f), lambda i, j: (0, j)),       # W_up chunk
        pl.BlockSpec((block_f, d), lambda i, j: (j, 0)),       # W_down chunk
    ]
    args = [x, w_gate if gated else w_up, w_up, w_down]

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_t, d), jnp.float32)],  # OS accumulator
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
