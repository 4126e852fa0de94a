"""The paper's core claim: all execution disciplines of a DSC block are
bit-identical — the fused dataflow changes WHEN, never WHAT."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dsc, quant
from repro.core.dsc import DSCBlockSpec
from repro.core.fusion import Schedule, dsc_block_pipelined, run_block


def _block(spec, hw, seed=0):
    key = jax.random.PRNGKey(seed)
    p32 = dsc.init_dsc_block_f32(key, spec)
    calib = np.asarray(jax.random.normal(jax.random.PRNGKey(seed + 1),
                                         (hw, hw, spec.cin)))
    qp = dsc.quantize_dsc_block(p32, spec, calib)
    x_q = jnp.asarray(quant.quantize(calib, qp.qp_in))
    return x_q, qp


SPECS = [
    (DSCBlockSpec(cin=8, cmid=48, cout=8, stride=1), 12),     # residual
    (DSCBlockSpec(cin=8, cmid=48, cout=16, stride=2), 12),    # downsample
    (DSCBlockSpec(cin=16, cmid=96, cout=16, stride=1), 10),   # paper 5th
    (DSCBlockSpec(cin=8, cmid=24, cout=8, stride=1), 7),      # odd H/W
    # t=1, no expansion: the depthwise reads the input
    (DSCBlockSpec(cin=8, cmid=8, cout=8, stride=1), 9),       # residual
    (DSCBlockSpec(cin=8, cmid=8, cout=16, stride=2), 11),     # s2, odd W
    (DSCBlockSpec(cin=32, cmid=32, cout=16, stride=1), 7),    # MNV2 first
]


@pytest.mark.parametrize("spec,hw", SPECS)
def test_all_schedules_bit_identical(spec, hw):
    x_q, qp = _block(spec, hw)
    ref = dsc.dsc_block_reference(x_q, qp)
    for sched in [Schedule.V1_PIXEL_SEQUENTIAL, Schedule.V2_INTER_STAGE,
                  Schedule.V3_INTRA_STAGE]:
        out = run_block(x_q, qp, sched)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out),
                                      err_msg=str(sched))


@pytest.mark.parametrize("tile_rows", [1, 2, 3, 5])
def test_rowtile_any_tiling_bit_identical(tile_rows):
    spec = DSCBlockSpec(cin=8, cmid=48, cout=8, stride=1)
    x_q, qp = _block(spec, 12)
    ref = dsc.dsc_block_reference(x_q, qp)
    out = dsc.dsc_block_fused_rowtile(x_q, qp, tile_rows=tile_rows)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_on_the_fly_padding_matches_explicit():
    """Fig 13: OTF padding (fused) == explicit padded tensor (reference).
    Covered implicitly above; this pins the boundary pixels explicitly."""
    spec = DSCBlockSpec(cin=8, cmid=48, cout=8, stride=1)
    x_q, qp = _block(spec, 6)
    ref = np.asarray(dsc.dsc_block_reference(x_q, qp))
    fused = np.asarray(dsc.dsc_block_fused_pixelwise(x_q, qp))
    # borders are exactly where padding matters
    np.testing.assert_array_equal(ref[0], fused[0])
    np.testing.assert_array_equal(ref[-1], fused[-1])
    np.testing.assert_array_equal(ref[:, 0], fused[:, 0])
    np.testing.assert_array_equal(ref[:, -1], fused[:, -1])


def test_pipeline_register_state_is_bounded():
    """v2's carry is one F1 tile + one F2 vector — independent of H, W.

    (The zero-buffer property, asserted structurally.)"""
    spec = DSCBlockSpec(cin=8, cmid=48, cout=8, stride=1)
    x_q, qp = _block(spec, 12)
    # jaxpr of the scan carry: (3,3,M) + (M,)
    jaxpr = jax.make_jaxpr(lambda x: dsc_block_pipelined(x, qp))(x_q)
    scan_eqs = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert scan_eqs, "pipelined impl must be a scan"
    eq = scan_eqs[0]
    nc, nk = eq.params["num_consts"], eq.params["num_carry"]
    carry_sizes = [int(np.prod(v.aval.shape))
                   for v in eq.invars[nc:nc + nk]]
    assert sum(carry_sizes) == 3 * 3 * spec.cmid + spec.cmid


def test_block_without_expansion_has_no_expansion_weights():
    """t=1 (cmid == cin) is MobileNetV2's published first bottleneck: no
    1x1 expansion, so no expansion weights and F1 in the input's domain;
    its depthwise pads with the input's zero point."""
    spec = DSCBlockSpec(cin=8, cmid=8, cout=16, stride=2)
    assert not spec.has_expansion
    assert DSCBlockSpec(cin=8, cmid=16, cout=8).has_expansion
    assert spec.macs(6, 6)["expansion"] == 0
    x_q, qp = _block(spec, 6)
    assert qp.w_exp is None and qp.b_exp is None and qp.m_exp is None
    assert qp.qp_f1 == qp.qp_in
    assert dsc.f1_zero_point(qp) == qp.qp_in.zero_point
    # the reference's depthwise sees exactly the zero-point-padded input
    f1 = np.pad(np.asarray(x_q), ((1, 1), (1, 1), (0, 0)),
                constant_values=qp.qp_in.zero_point).astype(np.int64)
    w = np.asarray(qp.w_dw, np.int64)
    acc = sum(f1[dy:dy + 5:2, dx:dx + 5:2] * w[dy, dx]
              for dy in range(3) for dx in range(3)) + np.asarray(qp.b_dw)
    f2 = np.asarray(quant.requantize(jnp.asarray(acc, jnp.int32), qp.m_dw,
                                     qp.qp_f2.zero_point, relu=True,
                                     relu6_max_q=qp.q6_f2))
    y = quant.requantize(dsc._projection_acc(jnp.asarray(f2), qp),
                         qp.m_proj, qp.qp_out.zero_point)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(dsc.dsc_block_reference(x_q,
                                                                     qp)))


@pytest.mark.parametrize("cin,cmid", [(8, 7), (16, 8)])
def test_expansion_never_shrinks(cin, cmid):
    with pytest.raises(ValueError, match="expands"):
        DSCBlockSpec(cin=cin, cmid=cmid, cout=8)
