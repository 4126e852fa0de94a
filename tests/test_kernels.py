"""Per-kernel shape/dtype sweeps against their oracles: ``core.dsc``'s
layer-by-layer reference for the int8 DSC kernel, ref.py for the rest.

Kernels run in interpret mode on this CPU container (TPU is the target).
The int8 DSC kernel must match EXACTLY; float kernels use dtype-scaled
tolerances.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dsc, quant
from repro.core.dsc import DSCBlockSpec
from repro.kernels import ops, ref
from repro.kernels.fused_ffn import fused_ffn_pallas
from repro.kernels.flash_attention import flash_attention
from repro.models import mobilenetv2 as mnv2


# --- fused DSC --------------------------------------------------------------


VWW_BLOCKS = [(name, spec, hw) for (name, spec), hw
              in zip(mnv2.block_specs(), mnv2.block_input_hw(80))]

# (spec, map size, tile_rows, seed): the block's weights come from PRNGKey
# seed, its input from seed + 1
DSC_CASES = [
    (DSCBlockSpec(cin=8, cmid=48, cout=8, stride=1), 12, 4, 0),
    (DSCBlockSpec(cin=8, cmid=48, cout=16, stride=2), 12, 3, 0),
    (DSCBlockSpec(cin=16, cmid=96, cout=16, stride=1), 10, 2, 0),
    (DSCBlockSpec(cin=8, cmid=24, cout=8, stride=1), 9, 5, 0),
    # ragged last tile: tile_rows does not divide h2 (the old fallback
    # silently degraded to the largest divisor — tile_rows=1 on primes)
    (DSCBlockSpec(cin=8, cmid=24, cout=8, stride=1), 13, 4, 0),  # h2=13
    (DSCBlockSpec(cin=8, cmid=24, cout=16, stride=2), 13, 4, 0),  # odd W
    (DSCBlockSpec(cin=8, cmid=24, cout=8, stride=2), 11, 4, 0),   # h2=6
    (DSCBlockSpec(cin=8, cmid=24, cout=8, stride=1), 7, 16, 0),   # tile>h2
] + [
    # every VWW block at its real widths and map size (stride 2 and the
    # 10x10 / 5x5 maps included)
    (spec, hw, 4, 3) for _, spec, hw in VWW_BLOCKS
] + [
    # blocks without expansion (t=1), the same kernel body with the
    # expansion left out: F1 is the input, padded with its zero point
    (DSCBlockSpec(cin=8, cmid=8, cout=8, stride=1), 9, 4, 5),    # residual
    (DSCBlockSpec(cin=8, cmid=8, cout=16, stride=2), 11, 4, 5),  # h2=6
    (DSCBlockSpec(cin=16, cmid=16, cout=8, stride=1), 7, 3, 5),  # ragged
    (DSCBlockSpec(cin=136, cmid=136, cout=24, stride=2), 9, 2, 5),  # 2 chunks
]
DSC_IDS = ([f"sweep{i}" for i in range(8)]
           + [f"vww-{name}" for name, _, _ in VWW_BLOCKS]
           + [f"t1-{i}" for i in range(4)])


@pytest.mark.parametrize("spec,hw,tile_rows,seed", DSC_CASES, ids=DSC_IDS)
def test_dsc_kernel_exact_vs_reference(spec, hw, tile_rows, seed):
    """The DSC kernel (``dsc_block``, or ``dw_block`` without expansion)
    plus the residual add where the block has one, through
    ``ops.dsc_block_q`` in interpret mode, bit-exact to the layer-by-layer
    reference."""
    p32 = dsc.init_dsc_block_f32(jax.random.PRNGKey(seed), spec)
    calib = np.asarray(jax.random.normal(jax.random.PRNGKey(seed + 1),
                                         (hw, hw, spec.cin)))
    qp = dsc.quantize_dsc_block(p32, spec, calib)
    x_q = jnp.asarray(quant.quantize(calib, qp.qp_in))
    got = ops.dsc_block_q(x_q, qp, tile_rows=tile_rows)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(dsc.dsc_block_reference(x_q, qp)))


# --- fused FFN --------------------------------------------------------------


@pytest.mark.parametrize("t,d,f", [(64, 128, 512), (32, 64, 192),
                                   (128, 128, 384)])
@pytest.mark.parametrize("act", ["silu", "gelu", "relu_sq"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_ffn_sweep(t, d, f, act, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (t, d), dtype)
    wg = (jax.random.normal(ks[1], (d, f), dtype) * 0.05).astype(dtype)
    wu = (jax.random.normal(ks[2], (d, f), dtype) * 0.05).astype(dtype)
    wd = (jax.random.normal(ks[3], (f, d), dtype) * 0.05).astype(dtype)
    got = fused_ffn_pallas(x, wg, wu, wd, act=act, block_t=32, block_f=128,
                           interpret=True)
    want = ref.fused_ffn_ref(x, wg, wu, wd, act=act)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_fused_ffn_ungated():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (64, 96), jnp.float32)
    wu = jax.random.normal(ks[1], (96, 256), jnp.float32) * 0.05
    wd = jax.random.normal(ks[2], (256, 96), jnp.float32) * 0.05
    got = fused_ffn_pallas(x, None, wu, wd, act="gelu", block_t=32,
                           block_f=64, interpret=True)
    want = ref.fused_ffn_ref(x, None, wu, wd, act="gelu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# --- flash attention --------------------------------------------------------


@pytest.mark.parametrize("tq,tk,d,causal,window,softcap", [
    (128, 128, 64, True, None, None),
    (256, 256, 64, True, None, 50.0),
    (128, 384, 64, False, None, None),
    (256, 256, 64, True, 64, None),
    (100, 100, 32, True, None, None),      # ragged
    (64, 160, 32, False, 48, None),        # window + ragged K
])
def test_flash_attention_sweep(tq, tk, d, causal, window, softcap):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (4, tq, d), jnp.float32)
    k = jax.random.normal(ks[1], (4, tk, d), jnp.float32)
    v = jax.random.normal(ks[2], (4, tk, d), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_mha_gqa_wrapper():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 64, 8, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 64, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 64, 2, 32), jnp.float32)
    o = ops.mha(q, k, v, n_kv_heads=2, causal=True, interpret=True)
    # oracle: repeat kv then full attention
    kr = jnp.repeat(k, 4, axis=2).transpose(0, 2, 1, 3).reshape(16, 64, 32)
    vr = jnp.repeat(v, 4, axis=2).transpose(0, 2, 1, 3).reshape(16, 64, 32)
    qr = q.transpose(0, 2, 1, 3).reshape(16, 64, 32)
    want = ref.attention_ref(qr, kr, vr, causal=True)
    want = want.reshape(2, 8, 64, 32).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), atol=2e-5)
