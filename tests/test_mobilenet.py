"""End-to-end MobileNetV2 int8: the paper's target network."""

import jax
import numpy as np
import pytest

from repro.core import quant
from repro.core.fusion import Schedule
from repro.models import mobilenetv2 as mnv2


@pytest.fixture(scope="module")
def net():
    return mnv2.init_and_quantize(jax.random.PRNGKey(0), img_hw=80)


@pytest.fixture(scope="module")
def img():
    return np.random.default_rng(0).standard_normal((80, 80, 3)).astype(np.float32)


def test_paper_blocks_have_paper_shapes(net):
    names = [n for n, *_ in mnv2.PAPER_BLOCKS]
    for want in ("3rd", "5th", "8th", "15th"):
        assert want in names
    # 5th block: F1/F2 = 20x20x96 => 38.4 KB buffer (paper §III-A)
    b5 = dict(zip(names, net.blocks))["5th"]
    assert b5.spec.cmid == 96
    assert 20 * 20 * 96 == 38_400


def test_all_schedules_end_to_end_identical(net, img):
    ref = np.asarray(mnv2.forward_int8(img, net,
                                       schedule=Schedule.V0_LAYER_BY_LAYER))
    for sched in (Schedule.V1_PIXEL_SEQUENTIAL, Schedule.V2_INTER_STAGE,
                  Schedule.V3_INTRA_STAGE):
        out = np.asarray(mnv2.forward_int8(img, net, schedule=sched))
        np.testing.assert_array_equal(ref, out, err_msg=str(sched))


def test_pallas_kernel_end_to_end_identical(net, img):
    """The compiled 80 px VWW program on the fast path with its DSC blocks
    in the Pallas kernel gives the reference's int8 logits."""
    from repro.cfu.compiler import compile_vww_network
    from repro.cfu.fastpath import fast_executor
    from repro.cfu.network import vww_cfu_params
    params = vww_cfu_params(net)
    prog = compile_vww_network(mnv2.block_specs(), 80, "fused")
    ex = fast_executor(prog, params, use_pallas=True)
    out = ex(np.asarray(quant.quantize(img, net.qp_img)), params)
    ref = np.asarray(mnv2.forward_int8(img, net, return_quantized=True))
    np.testing.assert_array_equal(out, ref)


def test_batched_inference(net):
    imgs = np.random.default_rng(1).standard_normal((4, 80, 80, 3)).astype(np.float32)
    logits = mnv2.forward_batch(imgs, net, schedule=Schedule.V3_INTRA_STAGE)
    assert logits.shape == (4, 2)
    assert np.isfinite(np.asarray(logits)).all()
