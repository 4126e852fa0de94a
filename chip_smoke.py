#!/usr/bin/env python3
"""Chip smoke test: the VWW fast path and its served path on one TPU chip.

    python3 chip_smoke.py            # from the repository root

Runs in one process and needs one TPU chip; with no TPU it exits non-zero
before doing any work. Phases, all at the deployment size of the int8
MobileNetV2-VWW network (80x80x3, stem + the seven DSC blocks at their real
widths + head 128 + GAP + FC; random weights from ``--seed``):

1. device check: the first JAX device must be a TPU;
2. fast path: the whole network compiled under ``fused``,
   ``fused-rowtile`` (Pallas stage bodies), ``layer-dram`` and
   ``fused-winograd`` (reference body; fused-winograd lowers its stride-2
   blocks fused, so those run the kernel), run through
   ``fastpath.FastPathExecutor`` at batch 1 and batch 8 on seeded images;
3. bit-exact check: every output equals the host-side golden word
   interpreter's (``executor.run_program``, pure numpy) under ``==``. The
   same four schedules also run the bare DSC chain at the stem-output size
   (40x40x8 in, 5x5x56 out), whose outputs are whole feature maps rather
   than two logits;
4. Pallas check: the fused and rowtile executors chose Pallas bodies and
   their compiled programs contain ``tpu_custom_call`` (compiled kernels,
   not interpret mode);
5. served path: ``repro.launch.serve_cfu.main`` at 80x80 with the fast
   backend and spot checks on (every 4th cross-checked against the
   interpreter).

Compile seconds are set-up facts, not benchmark metrics. Any failed check
or exception exits non-zero; the last line of a passing run is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SCHEDULES = ("fused", "fused-rowtile", "layer-dram", "fused-winograd")
PALLAS_SCHEDULES = ("fused", "fused-rowtile")
BATCHES = (1, 8)
SERVE_ARGS = ["--backend", "fast", "--img-hw", "80", "--requests", "300",
              "--rate", "100", "--spot-checks", "8"]


def _fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


class _CacheEvents:
    """Counts JAX's persistent compilation-cache hits and misses."""

    def __init__(self, jax):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _check_equal(label: str, got, want):
    import numpy as np
    if got.shape != want.shape:
        _fail(f"{label}: shape {got.shape} != golden {want.shape}")
    n_bad = int(np.count_nonzero(got != want))
    print(f"# bit-exact {label}: {'yes' if n_bad == 0 else 'NO'} "
          f"({n_bad} of {want.size} values differ)", flush=True)
    if n_bad:
        _fail(f"{label}: {n_bad} values differ from the golden interpreter")


def _run_executor(label: str, prog, params, images, check_pallas: bool):
    """Compile + run one program on the chip at every batch size and hold
    each output to the golden interpreter."""
    from repro.cfu import fastpath
    from repro.cfu.executor import run_program

    ex = fastpath.FastPathExecutor(prog, params)
    if check_pallas and not ex.use_pallas:
        _fail(f"{label}: executor did not choose the Pallas stage bodies")
    for b in BATCHES:
        x = images[0] if b == 1 else images[:b]
        t0 = time.perf_counter()
        y = ex(x, params)                  # first call: trace + compile + run
        first_s = time.perf_counter() - t0
        print(f"# setup {label} b{b}: first call {first_s:.2f} s "
              f"(compile included)", flush=True)
        _check_equal(f"{label} b{b}", y, run_program(prog, x, params))
    if check_pallas:
        text = ex.jitted.lower(images[:BATCHES[-1]],
                               ex.weights_of(params)).compile().as_text()
        n = text.count("tpu_custom_call")
        print(f"# pallas {label}: use_pallas={ex.use_pallas}, "
              f"{n} tpu_custom_call in the compiled program", flush=True)
        if n == 0:
            _fail(f"{label}: no tpu_custom_call in the compiled program")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        _fail(f"the repro package is not under {ROOT / 'src'} ({e})")
    cache_dir = enable_compile_cache()

    import jax
    import numpy as np

    # --- 1. device check ---------------------------------------------------
    devices = jax.devices()
    dev = devices[0]
    print(f"# jax {jax.__version__}, jaxlib {_version('jaxlib')}, "
          f"libtpu {_version('libtpu')}", flush=True)
    print(f"# device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        _fail(f"no TPU: JAX's first device is {dev.platform!r}; this "
              "script runs on the chip only")
    print(f"# compile cache: {cache_dir}", flush=True)
    cache = _CacheEvents(jax)

    from repro.cfu.compiler import compile_network, compile_vww_network
    from repro.cfu.network import random_chain_params, vww_cfu_params
    from repro.configs.vww import VWW
    from repro.core import quant
    from repro.launch import serve_cfu
    from repro.models import mobilenetv2 as mnv2

    # --- 2-4. fast path, bit-exact and Pallas checks ------------------------
    t0 = time.perf_counter()
    key = jax.random.PRNGKey(args.seed)
    net = mnv2.init_and_quantize(key, img_hw=VWW.img_hw,
                                 head_ch=VWW.head_ch, n_classes=VWW.n_classes)
    params = vww_cfu_params(net)
    specs = mnv2.block_specs()
    chain_hw = VWW.img_hw // 2                       # stem output size
    chain_params = random_chain_params(key, specs, chain_hw)
    rng = np.random.default_rng(args.seed)
    n = BATCHES[-1]
    imgs = rng.standard_normal(
        (n, VWW.img_hw, VWW.img_hw, VWW.img_ch)).astype(np.float32)
    imgs_q = np.asarray(quant.quantize(imgs, net.qp_img))
    maps = rng.standard_normal(
        (n, chain_hw, chain_hw, specs[0][1].cin)).astype(np.float32)
    maps_q = np.asarray(quant.quantize(maps, chain_params[0].qp_in))
    print(f"# setup: network quantized in {time.perf_counter() - t0:.2f} s",
          flush=True)

    for sched in SCHEDULES:
        prog = compile_vww_network(specs, VWW.img_hw, sched,
                                   img_ch=VWW.img_ch, head_ch=VWW.head_ch,
                                   n_classes=VWW.n_classes)
        _run_executor(f"vww{VWW.img_hw}/{sched}", prog, params, imgs_q,
                      check_pallas=sched in PALLAS_SCHEDULES)
        chain = compile_network(specs, chain_hw, chain_hw, sched)
        _run_executor(f"chain{chain_hw}/{sched}", chain, chain_params,
                      maps_q, check_pallas=sched in PALLAS_SCHEDULES)

    # --- 5. served path -----------------------------------------------------
    t0 = time.perf_counter()
    payload = serve_cfu.main(SERVE_ARGS + ["--seed", str(args.seed)])
    sc = payload.get("spot_checks") or {}
    print(f"# served path: {payload.get('n_served')} requests served in "
          f"{time.perf_counter() - t0:.2f} s host time, spot checks {sc}",
          flush=True)
    if payload.get("n_served") != payload.get("n_arrivals"):
        _fail(f"served {payload.get('n_served')} of "
              f"{payload.get('n_arrivals')} requests")
    if not sc.get("n_checks"):
        _fail("the served run executed no spot check")
    if not sc.get("all_bit_exact") or not sc.get("n_golden_cross"):
        _fail(f"served-path spot checks not all bit-exact or never "
              f"cross-checked against the interpreter: {sc}")

    print(f"# compile cache: {cache.hits} hits, {cache.misses} misses",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
