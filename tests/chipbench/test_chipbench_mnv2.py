"""The MobileNetV2 1.0 ImageNet cell, run whole on the CPU at a small size.

A checkout of its own holds the configuration at 16x16 images with every
published width (32-1280 channels, 1000 classes, the t=1 first block) and
4 images a call. A sound run is correct, the 4-bit control is not, a
traced run reads ``dw_kernel_roofline`` where a ``jit_dw_block`` kernel
ran and leaves it out where none did, and the reference's operations are
the hand count of arXiv:1801.04381 Table 2.
"""

import json
import pathlib
import shutil
import time
import types

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "mnv2-224-fused.batch256"
SEED = 2**31 + 8191          # more than 32 signed bits hold
MS = 1e6                     # ns


@pytest.fixture(scope="module")
def mnv2_root(tmp_path_factory):
    """A checkout of the benchmark whose MobileNetV2 cell runs at 16x16."""
    root = tmp_path_factory.mktemp("mnv2")
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    f = root / "chipbench" / "configs" / "mnv2-224-fused.json"
    cfg = json.loads(f.read_text())
    cfg["img_hw"] = 16
    f.write_text(json.dumps(cfg))
    (root / "chipbench" / "traffic" / "batch256.json").write_text(
        json.dumps({"loop": "closed", "batch": 4, "pool_batches": 2}))
    return root


@pytest.fixture(scope="module")
def harness():
    from chipbench import harness
    return harness


def _run(harness, root, trace=False):
    return harness.run_cell(CELL, SEED, 0.25, trace, time.perf_counter(),
                            check_devices=lambda c: jax.devices()[:c],
                            root=root, log=lambda *_: None)


def test_sound_run_is_correct(harness, mnv2_root):
    r = _run(harness, mnv2_root)
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] > 0
    assert all(v["value"] == 0 for v in r["check"].values())
    assert set(r["metrics"]) == {"images_per_s", "setup_s"}


def test_four_bit_control_is_not_correct(harness, mnv2_root):
    from chipbench import faults
    with faults.control():
        r = _run(harness, mnv2_root)
    assert r["correct"] is False
    assert r["check"]["mismatched_logits"]["value"] > 0


def test_traced_run_leaves_out_what_it_cannot_read(harness, mnv2_root,
                                                   monkeypatch):
    # the CPU has no row in the peaks table and no device plane: the
    # device-trace metrics, dw_kernel_roofline among them, stay out
    peaks = json.loads((ROOT / "chipbench/peaks.json").read_text())
    monkeypatch.setattr(harness, "peaks_of",
                        lambda kind: peaks["TPU v5 lite"])
    r = _run(harness, mnv2_root, trace=True)
    assert r["correct"] is True
    assert "dw_kernel_roofline" not in r["metrics"]


def _ref(root=ROOT):
    from chipbench import harness
    m = harness.load_manifest(root)
    return harness.load_config(m, "mnv2-224-fused", root)


def test_stage_costs_are_table_2_by_hand():
    cfg, ref = _ref()
    costs = {c["name"]: c for c in ref.stage_costs(cfg)}
    b1 = costs["b1"]              # 112x112x32 -> dw 3x3 -> 16, no expansion
    assert b1["kind"] == "dw"
    assert b1["macs"] == 112 * 112 * 9 * 32 + 112 * 112 * 32 * 16
    assert b1["macs"] == 10_035_200
    assert b1["map_bytes"] == 112 * 112 * 32 + 112 * 112 * 16
    assert b1["param_bytes"] == 9 * 32 + 32 * 16 + 8 * (32 + 16)
    assert [c["kind"] for c in costs.values()].count("dsc") == 16
    b2 = costs["b2"]              # 112x112x16 -> 96 -> s2 -> 56x56x24
    assert b2["macs"] == (112 * 112 * 16 * 96 + 56 * 56 * 9 * 96
                          + 56 * 56 * 96 * 24)
    assert costs["head"]["macs"] == 7 * 7 * 320 * 1280
    assert costs["gapfc"]["macs"] == 1280 * 1000
    assert sum(c["macs"] for c in costs.values()) == 300_774_272


def test_dw_kernel_roofline_reads_its_kernel_events(harness):
    """On a synthetic trace: least time of the dw blocks per call over the
    device time of the ``jit_dw_block`` events, none without them."""
    from chipbench import reduce
    from chipbench.reduce import Event, Trace
    cfg, ref = _ref()
    peaks = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e10}
    host = [Event(reduce.WINDOW, 0, 100 * MS),
            Event(reduce.CALL, 10 * MS, 40 * MS),
            Event(reduce.CALL, 50 * MS, 90 * MS)]
    ops = [Event("%vmap_jit_dw_block__.3", 12 * MS, 16 * MS),
           Event("%vmap_jit_dw_block__.3", 52 * MS, 56 * MS),
           Event("%vmap_jit_dsc_block__.4", 16 * MS, 30 * MS)]
    run = types.SimpleNamespace(
        trace=Trace({"/device:TPU:0": ops}, host), ref=ref, cfg=cfg,
        plan=types.SimpleNamespace(batch=4), peaks=peaks)
    read = harness.metric_reader("dw_kernel_roofline")
    b1 = next(c for c in ref.stage_costs(cfg) if c["kind"] == "dw")
    least = max(2 * b1["macs"] * 4 / 1e12,
                (b1["map_bytes"] * 4 + b1["param_bytes"]) / 1e10)
    assert read(run) == pytest.approx(100 * least * 2 / 8e-3)
    run.trace = Trace({"/device:TPU:0": ops[2:]}, host)
    assert read(run) is None
    run.trace = None
    assert read(run) is None
