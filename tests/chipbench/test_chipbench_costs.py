"""Operations and bytes from the shapes, against counts made by hand."""

import importlib.util
import json
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _ref():
    path = ROOT / "chipbench" / "configs" / "mobilenetv2_vww_int8.py"
    spec = importlib.util.spec_from_file_location("ref_for_costs", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def costs():
    cfg = json.loads((ROOT / "chipbench/configs/vww80-fused.json")
                     .read_text())
    return {c["name"]: c for c in _ref().stage_costs(cfg)}


def test_block_3rd_by_hand(costs):
    c = costs["3rd"]          # 40x40x8 -> 48 -> 8, stride 1
    assert c["macs"] == 614_400 + 691_200 + 614_400
    assert c["map_bytes"] == 40 * 40 * 8 * 2
    assert c["param_bytes"] == 8 * 48 + 9 * 48 + 48 * 8 + 8 * (2 * 48 + 8)


def test_stride2_block_counts_output_pixels(costs):
    c = costs["b2"]           # 40x40x8 -> 48 -> 16, stride 2 -> 20x20
    assert c["macs"] == 40 * 40 * 8 * 48 + 20 * 20 * 9 * 48 + \
        20 * 20 * 48 * 16
    assert c["map_bytes"] == 40 * 40 * 8 + 20 * 20 * 16


def test_per_image_total_is_16_9_mop(costs):
    macs = sum(c["macs"] for c in costs.values())
    assert costs["stem"]["macs"] == 345_600
    assert sum(c["macs"] for c in costs.values()
               if c["kind"] == "dsc") == 7_936_800
    assert costs["head"]["macs"] == 179_200
    assert costs["gapfc"]["macs"] == 256
    assert macs == 8_461_856
    assert 2 * macs == pytest.approx(16.9e6, rel=2e-3)


def test_roofline_least_time_and_bound():
    import importlib.util as u
    spec = u.spec_from_file_location(
        "roofline_reader", ROOT / "chipbench/metrics/dsc_kernel_roofline.py")
    mod = u.module_from_spec(spec)
    spec.loader.exec_module(mod)
    peaks = {"int8_ops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    costs = [{"macs": 100, "map_bytes": 1, "param_bytes": 0},   # 2 s ops
             {"macs": 1, "map_bytes": 30, "param_bytes": 10}]   # 4 s bytes
    t, bounds = mod.least_seconds(costs, 1, peaks)
    assert t == pytest.approx(2.0 + 4.0)
    assert bounds == ["compute", "memory"]
