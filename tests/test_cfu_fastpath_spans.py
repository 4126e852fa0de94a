"""The fast path's entry marks its phases on the profiler's clock.

A small executor is traced on the CPU with ``jax.profiler`` and the trace
read back with the benchmark's own reader (``chipbench.reduce.load``):

* every call holds the four phase spans once each, in order, on the
  caller's thread and nested in the call;
* the first call with a new input shape holds a ``fastpath.compile`` span
  in each of its two dispatches and a repeat holds none, and ``n_traces``
  counts the shapes;
* the spans carry their args: the first call uploads the stage arrays
  given as host arrays and every later call none, no call copies a stage
  array back from the device, and the input's bytes.
"""

import dataclasses
import glob
import os
import pathlib
import sys

import jax
import numpy as np
import pytest

from repro.cfu import fastpath
from repro.cfu.compiler import compile_network
from repro.core import dsc, quant
from repro.core.dsc import DSCBlockSpec

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import reduce  # noqa: E402

PHASES = ("fastpath.weights", "fastpath.put_input", "fastpath.launch",
          "fastpath.readback")
HW = 9
CHAIN = (DSCBlockSpec(cin=3, cmid=9, cout=5, stride=1),
         DSCBlockSpec(cin=5, cmid=10, cout=4, stride=2))
# (batch or None for one frame, a new input shape?) per call, in order
CALLS = ((3, True), (3, False), (2, True), (None, True), (2, False))


def _program():
    params, h = [], HW
    for i, spec in enumerate(CHAIN):
        p32 = dsc.init_dsc_block_f32(jax.random.PRNGKey(i), spec)
        calib = np.asarray(jax.random.normal(
            jax.random.PRNGKey(100 + i), (h, h, spec.cin)))
        params.append(dsc.quantize_dsc_block(p32, spec, calib))
        h, _ = spec.out_hw(h, h)
    # the first block's arrays stay on the device, the second's on the host
    params[1] = dataclasses.replace(params[1], **{
        n: np.asarray(getattr(params[1], n))
        for n in fastpath._STAGE_ARRAYS["dsc"]})
    prog = compile_network([(f"b{i}", s) for i, s in enumerate(CHAIN)],
                           HW, HW, "fused")
    rng = np.random.default_rng(0)
    x_f = rng.standard_normal((3, HW, HW, CHAIN[0].cin)).astype(np.float32)
    return prog, params, np.asarray(quant.quantize(x_f, params[0].qp_in))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced window of the calls in ``CALLS`` on a fresh executor."""
    prog, params, x_q = _program()
    ex = fastpath.FastPathExecutor(prog, params)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(reduce.WINDOW):
            for batch, _ in CALLS:
                x = x_q[:batch] if batch else x_q[0]
                with jax.profiler.TraceAnnotation(reduce.CALL):
                    ex(x, params)
    finally:
        jax.profiler.stop_trace()
    return ex, params, reduce.load(log_dir), log_dir


def _inside(trace, call):
    return [e for e in trace.host if e.line == call.line
            and e.start >= call.start and e.end <= call.end
            and e.name.startswith("fastpath.")]


@pytest.mark.parametrize("i", range(len(CALLS)))
def test_each_call_holds_the_phases_once_in_order(traced, i):
    _, _, trace, _ = traced
    calls = trace.spans(reduce.CALL)
    assert len(calls) == len(CALLS)
    spans = [e for e in _inside(trace, calls[i]) if e.name in PHASES]
    spans.sort(key=lambda e: e.start)
    assert [e.name for e in spans] == list(PHASES)
    for a, b in zip(spans, spans[1:]):
        assert a.end <= b.start
    # on the caller's thread, nowhere else
    n = sum(e.name in PHASES for e in trace.host)
    assert n == len(PHASES) * len(CALLS)


@pytest.mark.parametrize("i", range(len(CALLS)))
def test_compile_span_only_on_a_new_shape(traced, i):
    # one inside each dispatch of the call: the input's upload, the launch
    _, _, trace, _ = traced
    inside = _inside(trace, trace.spans(reduce.CALL)[i])
    compiles = [e for e in inside if e.name == "fastpath.compile"]
    for phase in ("fastpath.put_input", "fastpath.launch"):
        span = next(e for e in inside if e.name == phase)
        assert sum(span.start <= c.start and c.end <= span.end
                   for c in compiles) == int(CALLS[i][1]), phase
    assert len(compiles) == 2 * int(CALLS[i][1])


def test_n_traces_counts_the_input_shapes(traced):
    ex, _, _, _ = traced
    assert ex.n_traces == sum(new for _, new in CALLS) == 3


def test_span_args(traced):
    ex, params, _, log_dir = traced
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    stats = {}
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in PHASES[:3]:      # the spans with args
                    stats.setdefault(e.name, []).append(dict(e.stats))
    host = [(name, getattr(params[st.block], name)) for st in ex.stages
            for name in fastpath._STAGE_ARRAYS[st.kind]
            if not isinstance(getattr(params[st.block], name), jax.Array)]
    itemsize = {"w": 1, "b": 4, "m": 4}       # int8, int32, float32
    n_bytes = sum(np.size(v) * itemsize[name[0]] for name, v in host)
    n = 9 * len(CHAIN)
    assert len(host) == 9                     # the second block's
    # the first call uploads the host arrays, every later call none
    assert stats["fastpath.launch"] == [
        {"arrays": len(host), "bytes": n_bytes}] + [
        {"arrays": 0, "bytes": 0}] * (len(CALLS) - 1)
    assert stats["fastpath.weights"] == [
        {"d2h_arrays": 0, "reused": n - len(host), "uploaded": len(host)}
    ] + [{"d2h_arrays": 0, "reused": n, "uploaded": 0}] * (len(CALLS) - 1)
    assert (ex.weight_uploads, ex.weight_binds) == (len(host), 1)
    frame = HW * HW * CHAIN[0].cin
    assert [s["bytes"] for s in stats["fastpath.put_input"]] == [
        (b or 1) * frame for b, _ in CALLS]
