"""Observability layer: trace exactness, determinism, and the CSR bank.

The tracing contract, as tests:

* **Exactness** — the cost model's per-phase span durations sum to
  ``TimingReport.total_cycles`` bit-for-bit (they are computed by the
  same expression), per core, for every schedule x stream count x batch;
  the trace's byte counters equal the report's byte counters equal the
  paper's analytic ``core.traffic`` Eq. 1/2 counts.
* **Modeled == executed** — ``TimingReport.counter_bank()`` and
  ``ExecStats.counter_bank()`` diff to NOTHING on the non-cycle CSRs
  (bytes per space and direction, weight bytes, retired instructions
  per opcode, MACs per engine) for single streams at any batch and for
  the multi-core runner over one frame group per core.
* **Zero overhead, zero feedback** — the null tracer records nothing,
  and attaching a real tracer changes no computed number (the golden
  fingerprints are byte-identical with tracing on or off).
* **Determinism** — one seed fixes the serving trace JSON byte-for-byte.
* **Calibration hook** — ``handoff_sync_cycles`` reprices the
  double-buffer boundary sync without touching byte counts.
"""

import json

import numpy as np
import pytest

from repro.cfu import isa
from repro.cfu.compiler import CFUSchedule, compile_block, compile_network
from repro.cfu.executor import run_multistream, run_program
from repro.core.dsc import DSCBlockSpec
from repro.cfu.serve.planner import build_vww_service, simulate
from repro.cfu.timing import (HANDOFF_SYNC_CYCLES, BatchCostModel,
                              MultiStreamCostModel, analyze,
                              analyze_multistream)
from repro.cfu.trace import (CAT_PHASE, NULL_TRACER, CounterBank,
                             NullTracer, Tracer)
from repro.core import dsc, quant
from repro.core.traffic import block_traffic

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # optional extra; CI installs it
    HAVE_HYPOTHESIS = False

ALL_SCHEDULES = (CFUSchedule.LAYER_DRAM, CFUSchedule.LAYER_SRAM,
                 CFUSchedule.FUSED, CFUSchedule.FUSED_ROWTILE,
                 CFUSchedule.FUSED_WINOGRAD)

CHAIN = [("b0", DSCBlockSpec(cin=8, cmid=48, cout=8, stride=1)),
         ("b1", DSCBlockSpec(cin=8, cmid=48, cout=16, stride=2)),
         ("b2", DSCBlockSpec(cin=16, cmid=96, cout=16, stride=1))]
HW = 12


def _chain_params(seed=3):
    import jax
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((HW, HW, CHAIN[0][1].cin)).astype(np.float32)
    params = []
    for i, (_, spec) in enumerate(CHAIN):
        p32 = dsc.init_dsc_block_f32(jax.random.PRNGKey(i), spec)
        qp = dsc.quantize_dsc_block(p32, spec, x)
        params.append(qp)
        x = np.asarray(dsc.dsc_block_f32(x, p32, spec))
    rng = np.random.default_rng(seed + 1)
    x_f = rng.standard_normal((HW, HW, CHAIN[0][1].cin)).astype(np.float32)
    x_q = np.asarray(quant.quantize(x_f, params[0].qp_in))
    return x_q, params


@pytest.fixture(scope="module")
def chain_input():
    return _chain_params()


def _nonclock_diff(a: CounterBank, b: CounterBank) -> dict:
    """CSR deltas excluding the cycle CSRs (the executor has no clock)."""
    return {k: v for k, v in a.diff(b).items()
            if not k.endswith("_cycles")}


# --- exactness: spans sum to report totals ----------------------------------


@pytest.mark.parametrize("sched", ALL_SCHEDULES)
@pytest.mark.parametrize("streams", [1, 2])
@pytest.mark.parametrize("batch", [1, 3])
def test_span_cycles_sum_to_report_totals(sched, streams, batch):
    prog = compile_network(CHAIN, HW, HW, sched, streams=streams)
    tr = Tracer()
    if streams == 1:
        model = BatchCostModel(prog, "v3")
        rep = model.report(batch)
        end = model.emit_trace(tr, batch)
        assert tr.span_cycles(pid=0, cat=CAT_PHASE) == rep.total_cycles
        assert end == rep.total_cycles
    else:
        model = MultiStreamCostModel(prog, "v3")
        rep = model.report(batch)
        model.emit_trace(tr, batch)
        for i, r in enumerate(rep.per_stream):
            assert tr.span_cycles(pid=i, cat=CAT_PHASE) == r.total_cycles
        # stacked end-to-end: the whole timeline is the per-core sum
        # (aggregate per-core to keep float summation order identical)
        assert sum(tr.span_cycles(pid=i, cat=CAT_PHASE)
                   for i in range(len(rep.per_stream))) == \
            sum(r.total_cycles for r in rep.per_stream)


@pytest.mark.parametrize("sched", ALL_SCHEDULES)
def test_trace_counters_equal_report_and_analytic_bytes(sched):
    """Final cumulative byte counter == report bytes == Eq. 1/2 bytes."""
    name, spec = "solo", DSCBlockSpec(cin=8, cmid=48, cout=8, stride=1)
    hw = 12
    prog = compile_block(spec, hw, hw, sched)
    model = BatchCostModel(prog, "v3")
    rep = model.report(1)
    tr = Tracer()
    model.emit_trace(tr, 1)
    c = tr.last_counter("model.bytes", pid=0)
    assert int(c["dram_rd"] + c["dram_wr"]) == rep.dram_bytes
    assert int(c["sram_rd"] + c["sram_wr"]) == rep.sram_bytes
    t = block_traffic(spec, hw, hw, name)
    if sched == CFUSchedule.LAYER_DRAM:
        assert rep.dram_bytes == t.baseline_total
    elif sched == CFUSchedule.LAYER_SRAM:
        assert rep.dram_bytes == t.baseline_total - t.intermediate_bytes
        assert rep.sram_bytes == t.intermediate_bytes
    else:            # all fused schedules hit the paper's fused count
        assert rep.dram_bytes == t.fused_total


# --- modeled == executed (the CSR bank diff) --------------------------------


@pytest.mark.parametrize("sched", ALL_SCHEDULES)
@pytest.mark.parametrize("batch", [1, 2])
def test_executor_counters_match_model(sched, batch, chain_input):
    x_q, params = chain_input
    prog = compile_network(CHAIN, HW, HW, sched)
    rep = analyze(prog, "v3", batch=batch)
    xb = np.stack([x_q] * batch) if batch > 1 else x_q
    _, stats = run_program(prog, xb, params, return_stats=True)
    assert _nonclock_diff(rep.counter_bank(), stats.counter_bank()) == {}
    # field-level alignment (same names, same units, same values)
    assert stats.retired == rep.retired
    assert stats.macs_by_engine == rep.macs_by_engine
    assert stats.dram_rd_bytes == rep.dram_rd_bytes
    assert stats.dram_wr_bytes == rep.dram_wr_bytes
    assert stats.sram_rd_bytes == rep.sram_rd_bytes
    assert stats.sram_wr_bytes == rep.sram_wr_bytes
    assert stats.weight_bytes == rep.weight_bytes
    assert stats.n_macs == rep.macs


def test_multistream_executor_counters_match_model(chain_input):
    """One frame group: each core executes its stream exactly once, so
    per-core ExecStats must equal the per-stream model reports."""
    x_q, params = chain_input
    ms = compile_network(CHAIN, HW, HW, CFUSchedule.FUSED, streams=2)
    rep = analyze_multistream(ms, "v3", batch=1)
    _, stats = run_multistream(ms, x_q, params, return_stats=True)
    assert len(stats) == len(rep.per_stream) == 2
    for st_i, r_i in zip(stats, rep.per_stream):
        assert _nonclock_diff(r_i.counter_bank(),
                              st_i.counter_bank()) == {}


def test_executor_phase_spans_cover_all_instructions(chain_input):
    """Executor phase spans (instruction time) tile the whole stream:
    durations sum to retired instructions, no overlap, no gaps."""
    x_q, params = chain_input
    prog = compile_network(CHAIN, HW, HW, CFUSchedule.FUSED)
    tr = Tracer()
    _, stats = run_program(prog, x_q, params, return_stats=True,
                           tracer=tr)
    spans = tr.spans(pid=0)
    assert spans, "executor emitted no phase spans"
    assert sum(s["dur"] for s in spans) == stats.n_instr
    cursor = 0
    for s in spans:       # emission order is phase order
        assert s["ts"] == cursor
        cursor += s["dur"]


# --- zero overhead / zero feedback ------------------------------------------


def test_null_tracer_records_nothing():
    nt = NullTracer()
    nt.span("x", 0, 1)
    nt.counter("c", 0, 1)
    nt.instant("i", 0)
    nt.process_name(0, "p")
    nt.thread_name(0, 0, "t")
    nt.counter_bank(CounterBank(), 0)
    assert nt.events == []
    assert NULL_TRACER.events == []


def test_tracing_changes_no_computed_value(chain_input):
    x_q, params = chain_input
    prog = compile_network(CHAIN, HW, HW, CFUSchedule.FUSED_ROWTILE)
    y0, s0 = run_program(prog, x_q, params, return_stats=True)
    y1, s1 = run_program(prog, x_q, params, return_stats=True,
                         tracer=Tracer())
    np.testing.assert_array_equal(y0, y1)
    assert s0.counter_bank().as_csrs() == s1.counter_bank().as_csrs()
    assert s0.n_instr == s1.n_instr


# --- determinism + export format --------------------------------------------


def _tiny_serve_trace(seed=0, slo_cycles=None):
    service = build_vww_service(16, streams=1, freq_hz=300e6, max_batch=8)
    tr = Tracer()
    service.emit_model_trace(tr, 4, pid_base=100)
    simulate(service, "timeout", 400.0, n_requests=40, seed=seed,
             slo_cycles=slo_cycles, tracer=tr)
    return tr


def test_trace_json_deterministic_same_seed():
    a = _tiny_serve_trace(seed=7).to_json()
    b = _tiny_serve_trace(seed=7).to_json()
    assert a == b
    assert a != _tiny_serve_trace(seed=8).to_json()


def test_chrome_trace_format(tmp_path):
    tr = _tiny_serve_trace()
    path = tmp_path / "t.json"
    tr.save(str(path))
    doc = json.loads(path.read_text())
    assert doc["otherData"]["exporter"] == "repro.cfu.trace"
    evs = doc["traceEvents"]
    assert {"X", "C", "M"} <= {e["ph"] for e in evs}
    for e in evs:
        assert "pid" in e and "name" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0 and "ts" in e and "tid" in e
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert "serving (sim-cycle time)" in names
    assert any(n.startswith("core0-model") for n in names)


def test_serve_trace_contents():
    service = build_vww_service(16, streams=1, freq_hz=300e6, max_batch=8)
    tr = Tracer()
    res = simulate(service, "timeout", 400.0, n_requests=40, seed=0,
                   slo_cycles=1.0, tracer=tr)   # 1-cycle SLO: all violate
    n_batches = res.summary["n_batches"]
    batch_spans = [e for e in tr.events
                   if e["ph"] == "X" and e.get("cat") == "serve"]
    assert len(batch_spans) == n_batches
    depth_samples = [e for e in tr.events
                     if e["ph"] == "C" and e["name"] == "queue_depth"]
    assert len(depth_samples) == 40 + n_batches   # arrivals + dispatches
    instants = [e for e in tr.events if e["ph"] == "i"
                and e["name"] == "slo_violation"]
    assert len(instants) == res.summary["slo_violations"] == 40


# --- handoff calibration hook -----------------------------------------------


def test_handoff_sync_cycles_parameter():
    ms = compile_network(CHAIN, HW, HW, CFUSchedule.FUSED, streams=2)
    default = analyze_multistream(ms, "v3")
    free = analyze_multistream(ms, "v3", handoff_sync_cycles=0.0)
    pricey = analyze_multistream(ms, "v3", handoff_sync_cycles=1000.0)
    n_bounds = sum(r.n_dbuf_boundaries for r in default.per_stream)
    assert n_bounds > 0
    assert default.handoff_cycles == HANDOFF_SYNC_CYCLES * n_bounds
    assert free.handoff_cycles == 0.0
    assert pricey.handoff_cycles == 1000.0 * n_bounds
    # repricing the sync cost never touches byte counts or compute
    assert free.dram_bytes == default.dram_bytes == pricey.dram_bytes
    assert [r.total_cycles for r in free.per_stream] == \
        [r.total_cycles for r in default.per_stream]
    # the counter track reports the per-core boundary cost
    tr = Tracer()
    MultiStreamCostModel(ms, "v3", handoff_sync_cycles=1000.0
                         ).emit_trace(tr, 1)
    for i, r in enumerate(pricey.per_stream):
        c = tr.last_counter("model.handoff_cycles", pid=i)
        assert c["per_round"] == r.handoff_cycles
        assert c["n_boundaries"] == r.n_dbuf_boundaries


# --- CLI ---------------------------------------------------------------------


def test_serve_cfu_cli_trace(tmp_path):
    from repro.launch.serve_cfu import main
    out = tmp_path / "serve.json"
    main(["--rate", "300", "--requests", "30", "--img-hw", "16",
          "--spot-checks", "0", "--trace", str(out)])
    doc = json.loads(out.read_text())
    evs = doc["traceEvents"]
    # the acceptance invariant, re-checked from the FILE: model phase
    # span durations on the device lane sum to the device's report total
    service = build_vww_service(16, streams=1, freq_hz=300e6)
    want = service.report(service.max_batch).total_cycles
    got = sum(e["dur"] for e in evs
              if e["ph"] == "X" and e.get("cat") == CAT_PHASE
              and e["pid"] == 100)
    assert got == want
    assert any(e["ph"] == "X" and e.get("cat") == "serve" for e in evs)


def test_cfu_cli_trace(tmp_path):
    from repro.launch.cfu import main
    out = tmp_path / "cfu.json"
    main(["--net", "mobilenetv2", "--hw", "12", "--schedule", "fused",
          "--trace", str(out)])
    doc = json.loads(out.read_text())
    evs = doc["traceEvents"]
    model = sum(e["dur"] for e in evs if e["ph"] == "X"
                and e["pid"] == 100 and e.get("cat") == CAT_PHASE)
    execd = [e for e in evs if e["ph"] == "X" and e["pid"] == 0]
    assert model > 0 and execd   # both lanes landed in one file


def test_block_without_expansion_has_no_expansion_phase():
    """Two blocks under layer-dram, the first without expansion (t=1):
    the modelled timeline holds one expansion pass (the second block's),
    the first block adds nothing to the exp MAC counter, the executed
    counters equal the modelled ones, and the DRAM bytes equal Eq. 1
    with no F1 for the first block."""
    from repro.cfu.network import random_chain_params
    from repro.core.traffic import network_traffic
    import jax
    chain = [("a", DSCBlockSpec(cin=8, cmid=8, cout=8, stride=1)),
             ("b", DSCBlockSpec(cin=8, cmid=48, cout=16, stride=2))]
    hw = 10
    prog = compile_network(chain, hw, hw, CFUSchedule.LAYER_DRAM)
    model = BatchCostModel(prog, "v3")
    passes = [sorted(p.bound_stage_cycles) for p in model.phases
              if p.n_iters]
    assert passes == [["dw_mac", "dw_q"], ["pr_mac"],        # a: 2 passes
                      ["ex_mac", "ex_q"], ["dw_mac", "dw_q"], ["pr_mac"]]
    rep = model.report(1)
    alone = {n: analyze(compile_block(s, hw, hw, CFUSchedule.LAYER_DRAM))
             for n, s in chain}
    assert "exp" not in alone["a"].macs_by_engine
    assert rep.macs_by_engine["exp"] == alone["b"].macs_by_engine["exp"]
    assert rep.retired["EXP_MAC"] == hw * hw             # b's pixels only
    params = random_chain_params(jax.random.PRNGKey(2), chain, hw)
    x_q = np.random.default_rng(2).integers(-128, 128, (2, hw, hw, 8),
                                            dtype=np.int8)
    _, stats = run_program(prog, x_q, params, return_stats=True)
    assert _nonclock_diff(model.report(2).counter_bank(),
                          stats.counter_bank()) == {}
    tr = Tracer()
    model.emit_trace(tr, 1)
    c = tr.last_counter("model.bytes", pid=0)
    want = network_traffic([(n, s, hw, hw) for n, s in chain])
    assert int(c["dram_rd"] + c["dram_wr"]) == rep.dram_bytes == \
        want["baseline_total"]
    assert want["rows"][0].intermediate_bytes == 2 * hw * hw * 8   # F2 only


# --- hypothesis property -----------------------------------------------------


if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(data=st.data())
    def test_property_span_sums_and_analytic_bytes(data):
        """Any schedule x streams {1,2} x small geometry: span cycle
        sums equal report totals and DRAM bytes equal Eq. 1/2 counts."""
        sched = data.draw(st.sampled_from(ALL_SCHEDULES))
        streams = data.draw(st.integers(1, 2))
        batch = data.draw(st.integers(1, 3))
        cin = data.draw(st.integers(2, 8))
        spec = DSCBlockSpec(   # an inverted residual: cmid >= cin
            cin=cin,
            cmid=data.draw(st.integers(max(6, cin), 24)),
            cout=data.draw(st.integers(2, 8)),
            stride=data.draw(st.sampled_from([1, 2])))
        hw = data.draw(st.sampled_from([6, 8, 10]))
        specs = [("a", spec), ("b", spec)] if streams > 1 \
            else [("a", spec)]
        prog = compile_network(specs, hw, hw, sched, streams=streams)
        tr = Tracer()
        if streams == 1:
            m = BatchCostModel(prog, "v3")
            rep = m.report(batch)
            m.emit_trace(tr, batch)
            assert tr.span_cycles(pid=0, cat=CAT_PHASE) == \
                rep.total_cycles
            t = block_traffic(spec, hw, hw)
            if sched == CFUSchedule.LAYER_DRAM:   # one block: Eq. 1
                assert m.report(1).dram_bytes == t.baseline_total
        else:
            m = MultiStreamCostModel(prog, "v3")
            rep = m.report(batch)
            m.emit_trace(tr, batch)
            for i, r in enumerate(rep.per_stream):
                assert tr.span_cycles(pid=i, cat=CAT_PHASE) == \
                    r.total_cycles
