"""One run of one benchmark cell.

Everything a cell needs is found by name in ``BENCHMARK.json``:

* its configuration: the file the manifest names, whose ``network`` key
  names the plain reference module beside it in ``chipbench/configs/``;
* its traffic: ``chipbench/traffic/<traffic>.json``, read by
  ``chipbench/generator.py``;
* each metric: ``chipbench/metrics/<metric>.py``, whose ``read(run)``
  returns the number, or None when the run holds nothing to read.

A run makes the weights and a pool of inputs from the seed, builds the
program's public entry, warms up the cell's one input shape, and then
measures for ``--seconds`` (``setup_s`` is everything before that). Once
the window has closed and the device's peak memory is read, the program's
state is dropped and the plain reference computes the logits of every
input the window sent; every answer is compared with them.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import hashlib
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import generator, reduce

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".chipbench_cache"   # fixed: the path keys the cache
WARMUP_CALLS = 2
OVERRUN_S = 60.0        # an open loop stops serving this long after its due
LIMITS = {"mismatched_logits": 0, "unanswered": 0}


class NoChip(SystemExit):
    """The run found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What a run measured; the metric readers take their numbers here."""

    cfg: dict
    ref: object                     # the configuration's reference module
    plan: generator.Plan
    setup_s: float
    window_s: float                 # host clock, measured window
    images: int                     # images answered in the window
    latencies_ms: np.ndarray        # open loop: due -> logits on the host
    peaks: Optional[dict]           # the device's row of peaks.json
    trace: Optional[reduce.Trace] = None


# --------------------------------------------------------------------------
# Finding things by name
# --------------------------------------------------------------------------


def load_manifest(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_of(manifest: dict, workload: str) -> dict:
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    return cells[workload]


def metrics_of(manifest: dict, workload: str) -> Dict[str, List[dict]]:
    """The end-to-end and per-layer metrics this cell reports."""
    e2e = [m for m in manifest["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return {"end_to_end": e2e, "per_layer": layer}


def _load_module(path: pathlib.Path):
    """Import a file of the benchmark once per process, by its path."""
    name = "chipbench_file_" + hashlib.sha1(
        str(path.resolve()).encode()).hexdigest()[:16]
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]


def load_config(manifest: dict, name: str, root: pathlib.Path = ROOT):
    """The configuration's sizes and its plain reference module."""
    entry = {c["name"]: c for c in manifest["configs"]}[name]
    path = root / entry["file"]
    cfg = json.loads(path.read_text())
    ref_path = path.parent / f"{cfg['network']}.py"     # beside the file
    return cfg, _load_module(ref_path)


def metric_reader(name: str, root: pathlib.Path = ROOT
                  ) -> Callable[[Run], Optional[float]]:
    path = root / "chipbench" / "metrics" / f"{name}.py"
    return _load_module(path).read


def peaks_of(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# --------------------------------------------------------------------------
# The device
# --------------------------------------------------------------------------


def enable_compile_cache():
    """Every compile of this process goes through the persistent cache in
    the checkout, however short it was."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(chips: int):
    """The devices of this cell; a run without a TPU, or with fewer chips
    than the cell asks for, ends here."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"chipbench: JAX found no devices: {e}")
    if devices[0].platform != "tpu":
        raise NoChip(f"chipbench: no TPU (JAX's device is "
                     f"{devices[0].platform!r}); this benchmark runs on "
                     "the chip only")
    if len(devices) < chips:
        raise NoChip(f"chipbench: the cell needs {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices[:chips]


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class _CompileCounter:
    """Counts backend compiles (persistent-cache hits included)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


# --------------------------------------------------------------------------
# The measured window
# --------------------------------------------------------------------------


class _Spans:
    """Benchmark-side spans on the profiler's clock, in a traced run."""

    def __init__(self, on: bool):
        import jax
        self.on = on
        self._ann = jax.profiler.TraceAnnotation

    def __call__(self, name: str):
        return self._ann(name) if self.on else contextlib.nullcontext()


def _closed_loop(entry, pool, plan, seconds, spans):
    """Back-to-back calls cycling over the pool, for ``seconds``."""
    outs, order, calls = [], [], []
    t0 = time.perf_counter()
    with spans(reduce.WINDOW):
        while True:
            i = plan.order[len(outs) % len(plan.order)]
            start = time.perf_counter()
            with spans(reduce.CALL):
                outs.append(entry(pool[i]))
            end = time.perf_counter()
            calls.append((start - t0, end - start))
            order.append(i)
            if end - t0 >= seconds:
                break
    return np.stack(outs), np.array(order), end - t0, {"calls": calls}


def _open_loop(entry, pool, plan, spans):
    """Requests due at ``plan.arrivals_s``, one in flight, in order."""
    n = len(plan.arrivals_s)
    outs, lat, lag, calls = [], np.full(n, np.nan), [], []
    served, max_queue = 0, 0
    t0 = time.perf_counter()
    free_at = t0
    with spans(reduce.WINDOW):
        for k in range(n):
            due = t0 + plan.arrivals_s[k]
            now = time.perf_counter()
            if now - due > OVERRUN_S:
                break                        # the rest stay unanswered
            if now < due:
                with spans(reduce.WAIT):
                    if due - now > 2e-3:
                        time.sleep(due - now - 1e-3)
                    while time.perf_counter() < due:
                        pass
            start = time.perf_counter()
            if free_at <= due:               # idle at due: generator's lag
                lag.append(start - due)
            else:
                max_queue = max(max_queue, int(np.searchsorted(
                    plan.arrivals_s, start - t0, side="right")) - k)
            with spans(reduce.CALL):
                outs.append(entry(pool[plan.order[k]]))
            free_at = time.perf_counter()
            calls.append((start - t0, free_at - start))
            lat[k] = free_at - due
            served += 1
    t1 = time.perf_counter()
    lag = np.asarray(lag) * 1e3
    info = {"requests": n, "served": served, "max_queue": max_queue,
            "idle_at_due": int(lag.size),
            "lag_ms_p50": float(np.median(lag)) if lag.size else 0.0,
            "lag_ms_max": float(lag.max()) if lag.size else 0.0,
            "last_done_after_window_s": t1 - t0 - float(
                plan.arrivals_s[-1])}
    return (np.stack(outs) if outs else np.zeros((0, 0), np.int8),
            plan.order[:served], t1 - t0, {"latencies_ms": lat[:served] * 1e3,
                                          "info": info, "calls": calls})


# --------------------------------------------------------------------------
# One run
# --------------------------------------------------------------------------


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, check_devices=require_chips,
             root: pathlib.Path = ROOT, log=print) -> dict:
    """Set up, measure, check; returns the result line's object."""
    import jax

    manifest = load_manifest(root)
    cell = cell_of(manifest, workload)
    devices = check_devices(cell["chips"])
    cfg, ref = load_config(manifest, cell["config"], root)
    from chipbench import system

    plan = generator.plan(generator.load(cell["traffic"], root), seed,
                          seconds)
    marks = [("start", time.perf_counter() - t_start)]
    key = ref.seed_key(seed)
    weights = jax.block_until_ready(ref.make_weights(cfg, key))
    marks.append(("weights", time.perf_counter() - t_start))
    pool_dev = ref.images(cfg, key, plan.pool * plan.batch)
    pool = np.asarray(pool_dev)
    if plan.loop == "closed":
        pool = pool.reshape((plan.pool, plan.batch) + pool.shape[1:])
    marks.append(("inputs", time.perf_counter() - t_start))
    entry, ex = system.build(cfg, weights, ref)
    marks.append(("build", time.perf_counter() - t_start))
    for _ in range(WARMUP_CALLS):
        entry(pool[0])
    marks.append(("warm-up", time.perf_counter() - t_start))
    log("# setup (s from process start): " + ", ".join(
        f"{k} {v:.3f}" for k, v in marks))
    compiles = _CompileCounter()
    spans = _Spans(trace)
    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    pauses = []

    def on_gc(phase, info):
        pauses.append((phase, time.perf_counter()))
    gc.callbacks.append(on_gc)
    setup_s = time.perf_counter() - t_start
    if plan.loop == "closed":
        outs, order, window_s, extra = _closed_loop(entry, pool, plan,
                                                    seconds, spans)
    else:
        outs, order, window_s, extra = _open_loop(entry, pool, plan, spans)
    gc.callbacks.remove(on_gc)
    if trace:
        jax.profiler.stop_trace()
    n_compiles = compiles.n
    memory_peak = _memory_peak(devices)
    del entry, ex
    system.release()

    # ---- correctness: every answer of the window against the reference ----
    want = ref.logits(cfg, weights, pool_dev)
    want = want.reshape((plan.pool, -1) + want.shape[1:]) if \
        plan.loop == "closed" else want
    attempted = (len(order) * plan.batch if plan.loop == "closed"
                 else len(plan.arrivals_s) * plan.batch)
    answered = len(order) * plan.batch
    wrong = outs != want[order] if len(order) else np.zeros((0,), bool)
    bad_images = int(wrong.reshape(answered, -1).any(axis=1).sum()) \
        if answered else 0
    check = {"mismatched_logits": int(wrong.sum()),
             "unanswered": attempted - answered}
    saturated = float(np.mean(np.abs(want.astype(np.int32)) >= 127))
    log(f"# window: {window_s:.3f} s, {answered} images in "
        f"{len(order)} calls, {n_compiles} compiles inside")
    log(f"# reference: {want.size} logits, {saturated:.4f} of them at the "
        f"int8 bound")
    if "info" in extra:
        log("# generator: " + json.dumps(extra["info"]))
    gc_ms = np.diff([t for _, t in pauses]).reshape(-1)[::2] * 1e3 \
        if len(pauses) >= 2 else np.zeros(0)
    log(f"# gc in window: {gc_ms.size} collections, longest "
        f"{gc_ms.max() if gc_ms.size else 0.0:.3f} ms")
    if extra["calls"]:
        at, dur = np.array(extra["calls"]).T * 1e3
        slow = np.argsort(dur)[::-1][:3]
        log(f"# calls: median {np.median(dur):.3f} ms, slowest " + ", ".join(
            f"{dur[i]:.1f} ms at {at[i] / 1e3:.2f} s" for i in slow))

    dev0 = devices[0]
    run = Run(cfg=cfg, ref=ref, plan=plan,
              setup_s=setup_s, window_s=window_s, images=answered,
              latencies_ms=extra.get("latencies_ms", np.zeros(0)),
              peaks=None)
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    mets = metrics_of(manifest, workload)
    result = {"correct": None, "attempted": attempted,
              "failed": bad_images + check["unanswered"]}
    if trace:
        run.peaks = peaks_of(dev0.device_kind)
        run.trace = reduce.load(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
        lo, hi = run.trace.window()
        share = reduce.busy_share(run.trace)
        device["busy_s"] = (share or 0.0) * (hi - lo) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        chosen = mets["per_layer"]
    else:
        chosen = mets["end_to_end"]
    metrics = {}
    for m in chosen:
        value = metric_reader(m["name"], root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        result["breakdown"] = reduce.breakdown(run.trace)
    result["correct"] = all(check[k] <= LIMITS[k] for k in LIMITS)
    result["check"] = {k: {"value": check[k], "limit": LIMITS[k]}
                       for k in LIMITS}
    for k in LIMITS:
        print(f"check {k}: {check[k]} (limit {LIMITS[k]})", file=sys.stderr,
              flush=True)
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start)
    print(json.dumps(result), flush=True)
    return 0
