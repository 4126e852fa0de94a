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
  array back from the device, and the input's bytes and chunks;
* with the chunk limits lowered, a batch of 8 is uploaded and run in four
  chunks of 2: its logits equal the whole call's and the interpreter's,
  it compiles one chunk shape, ``chunked_calls`` counts it, and its trace
  holds ``weights``, then ``put_input`` and ``launch`` once per chunk, in
  turn, then ``readback``, each ``put_input`` with ``chunks`` = 4.
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
from repro.cfu.executor import run_program
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
    x_f = rng.standard_normal((8, HW, HW, CHAIN[0].cin)).astype(np.float32)
    return prog, params, np.asarray(quant.quantize(x_f, params[0].qp_in))


def _traced_calls(ex, params, inputs, log_dir):
    """The executor's logits of ``inputs``, each call one ``CALL`` span
    of one traced window; the trace as the benchmark reads it."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(reduce.WINDOW):
            out = []
            for x in inputs:
                with jax.profiler.TraceAnnotation(reduce.CALL):
                    out.append(ex(x, params))
    finally:
        jax.profiler.stop_trace()
    return out, reduce.load(log_dir)


def _arg_stats(log_dir):
    """The args of every ``fastpath.*`` span that has them, by name."""
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    stats = {}
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in PHASES[:3]:      # the spans with args
                    stats.setdefault(e.name, []).append(dict(e.stats))
    return stats


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced window of the calls in ``CALLS`` on a fresh executor."""
    prog, params, x_q = _program()
    ex = fastpath.FastPathExecutor(prog, params)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    _, trace = _traced_calls(
        ex, params, [x_q[:batch] if batch else x_q[0] for batch, _ in CALLS],
        log_dir)
    return ex, params, trace, log_dir


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
    stats = _arg_stats(log_dir)
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
    # every call of the window is small enough to go up whole
    assert [s["chunks"] for s in stats["fastpath.put_input"]] == [1] * len(
        CALLS)
    assert ex.chunked_calls == 0


# --- a call split into chunks -------------------------------------------------

FRAME_BYTES = HW * HW * CHAIN[0].cin
N_CHUNKED = 2                                 # chunked calls in the window


@pytest.fixture(scope="module")
def chunked(tmp_path_factory):
    """Two traced batch-8 calls on a fresh executor, with chunks of two
    images over a floor of two, so that each call goes up and runs in 4
    chunks of 2."""
    prog, params, x_q = _program()
    ex = fastpath.FastPathExecutor(prog, params)
    log_dir = str(tmp_path_factory.mktemp("chunked"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fastpath, "_CHUNK_MIN_BYTES", 2 * FRAME_BYTES)
        mp.setattr(fastpath, "_CHUNK_IMAGES", 2)
        assert fastpath._chunk_count(8, FRAME_BYTES) == 4
        out, trace = _traced_calls(ex, params, [x_q] * N_CHUNKED, log_dir)
    return prog, params, x_q, ex, out, trace, log_dir


def test_chunked_call_is_bit_exact(chunked):
    prog, params, x_q, _, out, _, _ = chunked
    whole = fastpath.FastPathExecutor(prog, params)
    want = whole(x_q, params)
    assert whole.chunked_calls == 0           # 8 images under the limits
    np.testing.assert_array_equal(want, run_program(prog, x_q, params))
    for y in out:
        assert y.shape == want.shape and y.dtype == np.int8
        np.testing.assert_array_equal(y, want)


def test_chunked_call_compiles_one_chunk_shape_and_counts(chunked):
    ex = chunked[3]
    assert ex.n_traces == 1                   # (2, 9, 9, 3), never (8, ...)
    assert ex.chunked_calls == N_CHUNKED


@pytest.mark.parametrize("i", range(N_CHUNKED))
def test_chunked_call_holds_the_phases_in_order(chunked, i):
    trace = chunked[5]
    calls = trace.spans(reduce.CALL)
    assert len(calls) == N_CHUNKED
    inside = _inside(trace, calls[i])
    spans = sorted((e for e in inside if e.name in PHASES),
                   key=lambda e: e.start)
    assert [e.name for e in spans] == [PHASES[0]] + list(PHASES[1:3]) * 4 \
        + [PHASES[3]]
    for a, b in zip(spans, spans[1:]):
        assert a.end <= b.start
    # the first call compiles the chunk shape once for each of its two
    # jitted functions, in its first chunk; the second call nothing
    compiles = [e for e in inside if e.name == "fastpath.compile"]
    assert len(compiles) == 2 * int(i == 0)
    for c in compiles:
        assert spans[1].start <= c.start and c.end <= spans[2].end


def test_chunked_call_span_args(chunked):
    _, params, _, ex, _, _, log_dir = chunked
    stats = _arg_stats(log_dir)
    assert stats["fastpath.put_input"] == [
        {"bytes": 2 * FRAME_BYTES, "chunks": 4}] * 4 * N_CHUNKED
    # the first chunk's launch uploads the second block's host arrays,
    # every other none
    launch = stats["fastpath.launch"]
    assert len(launch) == 4 * N_CHUNKED
    assert launch[0]["arrays"] == ex.weight_uploads == 9
    assert launch[1:] == [{"arrays": 0, "bytes": 0}] * (4 * N_CHUNKED - 1)
