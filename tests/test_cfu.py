"""CFU instruction-level simulator: the golden executor must be bit-exact
vs core/dsc (exact integer equality, same discipline as test_dsc), the
binary ISA must round-trip, and the timing model's measured bytes must
equal core/traffic's analytic Eq. 1/2 counts exactly."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cfu import isa
from repro.cfu.compiler import (AUTO_HETERO, AUTO_SCHEDULE, CFUSchedule,
                                compile_block, compile_network,
                                compile_vww_network, hetero_pe_candidates,
                                split_pe_budget)
from repro.cfu.executor import (HandoffViolation, MultiStreamRunner,
                                run_multistream, run_program, run_words)
from repro.cfu.ir import Layout, MemoryPlanError
from repro.cfu.network import random_chain_params, vww_cfu_params
from repro.cfu.timing import (PEConfig, analyze, analyze_multistream)
from repro.core import dsc, quant
from repro.core.dsc import DSCBlockSpec
from repro.core.fusion import Schedule, modeled_cycles
from repro.core.traffic import block_traffic, min_sram_buffer_bytes
from repro.models.mobilenetv2 import block_specs


@functools.lru_cache(maxsize=None)
def _block(spec, hw, seed=0):
    """Cached per (spec, hw): the JAX reference trace dominates runtime and
    is identical across the three schedule parametrizations."""
    key = jax.random.PRNGKey(seed)
    p32 = dsc.init_dsc_block_f32(key, spec)
    calib = np.asarray(jax.random.normal(jax.random.PRNGKey(seed + 1),
                                         (hw, hw, spec.cin)))
    qp = dsc.quantize_dsc_block(p32, spec, calib)
    x_q = np.asarray(quant.quantize(calib, qp.qp_in))
    ref = np.asarray(dsc.dsc_block_reference(x_q, qp))
    return x_q, qp, ref


# Randomized coverage: stride 1/2, residual/non-residual, odd sizes,
# channel counts that are not multiples of anything convenient.
SPECS = [
    (DSCBlockSpec(cin=8, cmid=48, cout=8, stride=1), 12),    # residual
    (DSCBlockSpec(cin=8, cmid=48, cout=16, stride=2), 12),   # downsample
    (DSCBlockSpec(cin=16, cmid=96, cout=16, stride=1), 10),  # paper 5th
    (DSCBlockSpec(cin=5, cmid=30, cout=7, stride=1), 9),     # odd dims
    (DSCBlockSpec(cin=4, cmid=24, cout=4, stride=2), 7),     # odd hw, s2
    (DSCBlockSpec(cin=6, cmid=18, cout=6, stride=1), 6),     # residual, tiny
    # t=1, no expansion: no EXP weights or MACs, the depthwise reads IN
    (DSCBlockSpec(cin=6, cmid=6, cout=6, stride=1), 7),      # residual
    (DSCBlockSpec(cin=5, cmid=5, cout=9, stride=2), 9),      # odd hw, s2
]


@pytest.mark.parametrize("spec,hw", SPECS)
@pytest.mark.parametrize("sched", list(CFUSchedule))
def test_executor_bit_exact_vs_reference(spec, hw, sched):
    x_q, qp, ref = _block(spec, hw, seed=(spec.cin * 31 + spec.cmid) % 97)
    prog = compile_block(spec, hw, hw, sched)
    y = run_program(prog, x_q, [qp])  # encodes, then runs from the words
    np.testing.assert_array_equal(y, ref, err_msg=str(sched))


def test_executor_matches_fused_pixelwise_exactly():
    spec, hw = DSCBlockSpec(cin=8, cmid=48, cout=8, stride=1), 8
    x_q, qp, _ = _block(spec, hw)
    prog = compile_block(spec, hw, hw, CFUSchedule.FUSED)
    y = run_program(prog, x_q, [qp])
    fused = np.asarray(dsc.dsc_block_fused_pixelwise(x_q, qp))
    np.testing.assert_array_equal(y, fused)


def test_network_chain_bit_exact():
    """The whole MobileNetV2 DSC chain as ONE instruction stream."""
    specs = block_specs()
    hw = 12
    rng = np.random.default_rng(3)
    x = rng.standard_normal((hw, hw, specs[0][1].cin)).astype(np.float32)
    params = []
    for i, (name, spec) in enumerate(specs):
        p32 = dsc.init_dsc_block_f32(jax.random.PRNGKey(i), spec)
        qp = dsc.quantize_dsc_block(p32, spec, x)
        params.append(qp)
        x = np.asarray(dsc.dsc_block_f32(x, p32, spec))
    rng = np.random.default_rng(4)
    x_f = rng.standard_normal((hw, hw, specs[0][1].cin)).astype(np.float32)
    x_q = np.asarray(quant.quantize(x_f, params[0].qp_in))
    ref = x_q
    for qp in params:
        ref = np.asarray(dsc.dsc_block_reference(ref, qp))
    for sched in CFUSchedule:
        prog = compile_network(specs, hw, hw, sched)
        y = run_program(prog, x_q, params)
        np.testing.assert_array_equal(y, ref, err_msg=str(sched))


# --- new schedules: fused-rowtile ------------------------------------------


@pytest.mark.parametrize("tile_rows", [1, 2, 3, 5])
def test_rowtile_matches_rowtile_reference_and_pallas(tile_rows):
    """The fused-rowtile stream must equal the row-tile JAX discipline
    (core.fusion v3's dataflow) AND the Pallas kernel, bit-exactly."""
    from repro.kernels.fused_dsc import fused_dsc_pallas
    spec, hw = DSCBlockSpec(cin=8, cmid=48, cout=8, stride=1), 12
    x_q, qp, ref = _block(spec, hw)
    prog = compile_block(spec, hw, hw, CFUSchedule.FUSED_ROWTILE,
                         tile_rows=tile_rows)
    y = run_program(prog, x_q, [qp])
    rt = np.asarray(dsc.dsc_block_fused_rowtile(jnp.asarray(x_q), qp,
                                                tile_rows=tile_rows))
    np.testing.assert_array_equal(y, rt)
    zps = (qp.qp_in.zero_point, qp.qp_f1.zero_point,
           qp.qp_f2.zero_point, qp.qp_out.zero_point)
    pl = fused_dsc_pallas(jnp.asarray(x_q), qp.w_exp,
                          qp.w_dw.reshape(9, spec.cmid), qp.w_proj,
                          qp.b_exp, qp.b_dw, qp.b_proj, qp.m_exp, qp.m_dw,
                          qp.m_proj, stride=spec.stride, zps=zps,
                          q6=(qp.q6_f1, qp.q6_f2), tile_rows=tile_rows,
                          interpret=True)
    y_pl = np.asarray(pl)
    if spec.has_residual:
        y_pl = np.asarray(dsc.residual_add_q(jnp.asarray(y_pl),
                                             jnp.asarray(x_q), qp))
    np.testing.assert_array_equal(y, y_pl)
    np.testing.assert_array_equal(y, ref)


@pytest.mark.parametrize("bi", range(7))
def test_rowtile_moves_no_more_dram_than_fused(bi):
    """Halo reuse across row tiles: rowtile's DRAM traffic equals the
    fused dataflow's exactly (each input byte fetched once; strip
    intermediates live in SRAM), and expansion recompute is gone (layer
    MAC count, not the fused 9x)."""
    (name, spec), hw = block_specs()[bi], MOBILENET_CHAIN_HW[bi]
    rep_rt = analyze(compile_block(spec, hw, hw, CFUSchedule.FUSED_ROWTILE))
    rep_f = analyze(compile_block(spec, hw, hw, CFUSchedule.FUSED))
    rep_d = analyze(compile_block(spec, hw, hw, CFUSchedule.LAYER_DRAM))
    assert rep_rt.dram_bytes == rep_f.dram_bytes
    assert rep_rt.macs == rep_d.macs          # expansion once per input row
    assert rep_rt.macs < rep_f.macs           # fused pays the 9x recompute
    # the strip is a few rows, not the Eq. 2 full-map buffer
    assert 0 < rep_rt.sram_buffer_bytes \
        < min_sram_buffer_bytes(spec, hw, hw) + spec.cmid * hw * 3


# --- scheduling passes -------------------------------------------------------


def test_auto_schedule_never_loses_to_uniform():
    """The cost-model pick is per block, so the auto stream's cycles are
    <= every uniform schedule's (per-block costs are additive across the
    chain: phases are per-block)."""
    specs = block_specs()
    hw = 16
    auto = analyze(compile_network(specs, hw, hw, AUTO_SCHEDULE), "v3")
    uniform = {s: analyze(compile_network(specs, hw, hw, s), "v3")
               for s in CFUSchedule}
    for s, rep in uniform.items():
        assert auto.total_cycles <= rep.total_cycles * (1 + 1e-9), s
    # and the picks genuinely mix (the point of per-block scheduling)
    prog = compile_network(specs, hw, hw, AUTO_SCHEDULE)
    assert len(set(prog.meta["block_schedules"].values())) > 1


def test_per_block_schedule_mapping_bit_exact():
    """An explicitly mixed per-block mapping executes bit-exactly."""
    specs = block_specs()[:4]
    hw = 10
    params = random_chain_params(jax.random.PRNGKey(3), specs, hw)
    mapping = {"3rd": "fused", "b2": "layer-sram",
               "5th": "fused-rowtile", "b4": "layer-dram"}
    prog = compile_network(specs, hw, hw, mapping)
    assert prog.meta["schedule"] == "mixed"
    assert prog.meta["block_schedules"] == mapping
    rng = np.random.default_rng(9)
    x_q = rng.integers(-128, 128, (hw, hw, specs[0][1].cin)).astype(np.int8)
    ref = x_q
    for qp in params:
        ref = np.asarray(dsc.dsc_block_reference(ref, qp))
    np.testing.assert_array_equal(run_program(prog, x_q, params), ref)


# --- memory planner ----------------------------------------------------------


def test_layout_add_raises_on_live_overlap():
    """Overlap is no longer silent: two live regions may not collide;
    freeing one legalizes address reuse (disjoint lifetimes)."""
    lay = Layout()
    lay.add("a", isa.SPACE_SRAM, 0, 100)
    with pytest.raises(MemoryPlanError):
        lay.add("b", isa.SPACE_SRAM, 50, 100)     # overlaps live 'a'
    lay.add("c", isa.SPACE_DRAM, 50, 100)         # other space: fine
    lay.free("a")
    lay.add("b", isa.SPACE_SRAM, 50, 100)         # 'a' freed: reuse is legal
    assert lay.sram_size == 150                   # high-water, not sum
    assert "a" in lay.regions                     # record survives the free


def test_memory_planner_reuses_scratch_across_blocks():
    """Liveness-driven placement: the SRAM high-water equals the LARGEST
    block's F1+F2 footprint (buffers of different blocks share addresses),
    and block-IO DRAM maps are reused once dead (footprint < the sum)."""
    specs = block_specs()
    hw = 16
    prog = compile_network(specs, hw, hw, CFUSchedule.LAYER_SRAM)
    lay = prog.meta["layout"]
    h = w = hw
    per_block = []
    for _, spec in specs:
        h2, w2 = spec.out_hw(h, w)
        per_block.append(h * w * spec.cmid + h2 * w2 * spec.cmid)
        h, w = spec.out_hw(h, w)
    assert lay.sram_size == max(per_block)
    io_sum = sum(r.size for r in lay.regions.values()
                 if r.space == isa.SPACE_DRAM)
    assert lay.dram_size < io_sum                 # dead maps were reused


def test_multistream_plan_pins_boundaries_not_scratch():
    """The shared-DRAM multi-core plan pins every IO map (the frame
    pipeline needs them all, every round) and places DRAM scratch in
    per-SEGMENT arenas: consecutive blocks of ONE core reuse their arena
    (never per-block copies), but scratch can never alias another core's
    data or a pinned boundary copy — every core re-executes its segment
    each round, so program-order liveness would be a lie."""
    specs = block_specs()
    hw = 16
    ms = compile_network(specs, hw, hw, CFUSchedule.LAYER_DRAM, streams=2)
    lay = ms.meta["layout"]
    io_sum = scratch_sum = 0
    per_block = {}
    for r in lay.regions.values():
        if r.name.startswith(("f1@", "f2@")):
            scratch_sum += r.size
            blk = r.name.split("@", 1)[1]
            per_block[blk] = per_block.get(blk, 0) + r.size
        else:
            io_sum += r.size
    # one reused arena per core: its high-water is its largest block
    arena_sum = sum(max(per_block[b] for b in seg if b in per_block)
                    for seg in ms.meta["partition"]
                    if any(b in per_block for b in seg))
    # every boundary map is pinned (ping AND pong count toward io_sum)...
    assert lay.dram_size >= io_sum
    # ...scratch adds one reused arena per segment, not per-block copies
    assert lay.dram_size <= io_sum + arena_sum
    assert lay.dram_size < io_sum + scratch_sum
    # scratch may NEVER alias pinned data (boundary copies live across
    # rounds; a core's scratch recurs every round)
    pinned = [r for r in lay.regions.values()
              if not r.name.startswith(("f1@", "f2@"))]
    scratch = [r for r in lay.regions.values()
               if r.name.startswith(("f1@", "f2@"))]
    for s in scratch:
        for p in pinned:
            assert not s.overlaps(p), (s, p)


def test_multistream_plan_double_buffers_boundaries():
    """Every inter-core boundary (and the host-facing program IO) gets a
    ping AND a pong copy: equal sizes, disjoint from each other and from
    everything else in DRAM."""
    specs = block_specs()
    ms = compile_network(specs, 12, 12, CFUSchedule.FUSED, streams=3)
    lay = ms.meta["layout"]
    bnd = ms.meta["boundaries"]
    # program input, program output, and N-1 inter-core maps
    assert ms.meta["in_region"] in bnd and ms.meta["out_region"] in bnd
    assert len(bnd) == len(ms.streams) + 1
    for name in bnd:
        ping, pong = lay.regions[name], lay.dbuf[name]
        assert ping.size == pong.size
        assert not ping.overlaps(pong)
    # the streams actually bind them with CFG_DBUF words
    for i, p in enumerate(ms.streams):
        dbuf_words = [ins for ins in p.instrs if ins.op == "CFG_DBUF"]
        assert dbuf_words, f"stream {i} binds no double-buffered boundary"
    # ...and each stream opens with its core slot
    for i, p in enumerate(ms.streams):
        assert ("CFG_CORE", (i, len(ms.streams))) in [
            (ins.op, ins.args) for ins in p.instrs[:3]]


# --- multi-stream compilation ------------------------------------------------


@pytest.mark.parametrize("streams", [2, 3])
def test_multistream_bit_exact_vs_single(streams):
    """N per-core streams over the shared DRAM plan produce exactly the
    single-stream result on the bare DSC chain, batched and unbatched."""
    specs = block_specs()
    hw = 12
    params = random_chain_params(jax.random.PRNGKey(1), specs, hw)
    rng = np.random.default_rng(streams)
    x_q = rng.integers(-128, 128, (2, hw, hw, specs[0][1].cin)) \
        .astype(np.int8)
    single = compile_network(specs, hw, hw, CFUSchedule.FUSED)
    ms = compile_network(specs, hw, hw, CFUSchedule.FUSED, streams=streams)
    assert len(ms.streams) == streams
    ref = run_program(single, x_q, params)
    np.testing.assert_array_equal(run_multistream(ms, x_q, params), ref)
    np.testing.assert_array_equal(run_multistream(ms, x_q[0], params),
                                  ref[0])


def test_multistream_vww_bit_exact_vs_forward_int8():
    """Full-VWW multistream: the partition has to handle the Conv3x3 stem
    unit and the indivisible GAP+FC unit at segment boundaries; the
    pipelined cores must still match the scalar-core reference logits."""
    from repro.models import mobilenetv2 as mnv2
    img_hw = 16
    net = mnv2.init_and_quantize(jax.random.PRNGKey(4), img_hw=img_hw)
    specs = block_specs()
    params = vww_cfu_params(net)
    rng = np.random.default_rng(11)
    imgs = rng.standard_normal((3, img_hw, img_hw, 3)).astype(np.float32)
    imgs_q = np.asarray(quant.quantize(imgs, net.qp_img))
    ref = np.asarray(mnv2.forward_batch(imgs, net, return_quantized=True))
    for streams in (2, 4):
        ms = compile_vww_network(specs, img_hw, CFUSchedule.FUSED,
                                 streams=streams)
        assert ms.meta["partition"][0][0] == "stem"
        assert ms.meta["partition"][-1][-2:] == ["gap", "fc"]
        np.testing.assert_array_equal(run_multistream(ms, imgs_q, params),
                                      ref, err_msg=f"streams={streams}")


def test_plan_memory_pin_is_not_destructive():
    """pin_io is a planning-time view: re-planning the same IR without the
    pin must recover the lifetime-aware (smaller) footprint."""
    from repro.cfu.compiler import assign_schedules, materialize_scratch
    from repro.cfu.ir import build_chain_ir, plan_memory
    specs = block_specs()
    ir = build_chain_ir(specs, 16, 16)
    assign_schedules(ir, CFUSchedule.FUSED)
    materialize_scratch(ir)
    unpinned = plan_memory(ir).dram_size
    pinned = plan_memory(ir, pin_io=True).dram_size
    assert pinned > unpinned
    assert plan_memory(ir).dram_size == unpinned      # pin didn't stick


def test_multistream_timing_interval_and_contention():
    """Steady-state model: the round interval is bounded below by the
    slowest core's round (compute/transfer + its double-buffer handoffs)
    and by the serialized DRAM port; total traffic equals the
    single-stream compile's (partitioning moves no extra bytes — the
    ping/pong copies alternate addresses, they don't duplicate traffic)."""
    specs = block_specs()
    hw = 12
    single = analyze(compile_network(specs, hw, hw, CFUSchedule.FUSED), "v3")
    ms = compile_network(specs, hw, hw, CFUSchedule.FUSED, streams=3)
    rep = analyze_multistream(ms, "v3")
    assert len(rep.per_stream) == 3
    # every core syncs on at least its in+out boundary, each round
    assert all(r.n_dbuf_boundaries >= 2 for r in rep.per_stream)
    assert rep.handoff_cycles == pytest.approx(
        sum(r.handoff_cycles for r in rep.per_stream))
    slowest = max(r.total_cycles + r.handoff_cycles
                  for r in rep.per_stream)
    port = sum(r.dram_transfer_cycles for r in rep.per_stream)
    assert rep.interval_cycles == pytest.approx(max(slowest, port))
    assert rep.interval_cycles <= rep.latency_cycles
    assert rep.dram_contention_cycles == pytest.approx(
        max(0.0, port - slowest))
    assert rep.dram_bytes == single.dram_bytes
    assert rep.throughput_speedup_vs_single > 1.0
    assert rep.pipeline_fill_cycles == pytest.approx(
        2 * rep.interval_cycles)
    # per-round latency is the sum of the cores (they run back-to-back)
    assert rep.latency_cycles == pytest.approx(
        sum(r.total_cycles + r.handoff_cycles for r in rep.per_stream))


# --- heterogeneous frame pipeline: handoff, batching, per-core PEs -----------


def _ms_fixture(streams=2, hw=8, n_frames=4, seed=3):
    specs = [("b0", DSCBlockSpec(cin=4, cmid=8, cout=6, stride=2)),
             ("b1", DSCBlockSpec(cin=6, cmid=12, cout=5, stride=1)),
             ("b2", DSCBlockSpec(cin=5, cmid=10, cout=7, stride=1))]
    params = random_chain_params(jax.random.PRNGKey(seed), specs, hw,
                                 seed=seed)
    rng = np.random.default_rng(seed)
    x_q = rng.integers(-128, 128, (n_frames, hw, hw, 4)).astype(np.int8)
    single = compile_network(specs, hw, hw, CFUSchedule.FUSED)
    ref = run_program(single, x_q, params)
    ms = compile_network(specs, hw, hw, CFUSchedule.FUSED, streams=streams)
    return ms, x_q, params, ref


def test_handoff_violation_raises_not_stale_reads():
    """A core may not read a boundary copy before its producer's round
    retired: stepping the consumer first RAISES instead of silently
    executing on stale (zero-initialized) data."""
    ms, x_q, params, _ = _ms_fixture()
    r = MultiStreamRunner(ms, x_q, params)
    with pytest.raises(HandoffViolation, match="has not retired"):
        r.step(1)
    # ...and the producer may not run further than the two copies allow:
    # groups 0 and 1 fill ping and pong, group 2 would clobber unconsumed
    # ping data.
    r.step(0)
    r.step(0)
    with pytest.raises(HandoffViolation, match="consumer has not drained"):
        r.step(0)
    # draining unblocks exactly one more producer round
    r.step(1)
    r.step(0)


def test_handoff_legal_out_of_order_schedule_bit_exact():
    """The double buffer admits schedules other than the canonical round
    interleave (producer up to two groups ahead); any legal order reaches
    the bit-exact result."""
    ms, x_q, params, ref = _ms_fixture(n_frames=5)
    r = MultiStreamRunner(ms, x_q, params)
    # greedy: always step the most-starved ready core, producer-biased
    while not r.done:
        for core in (0, 1):
            if r.ready(core):
                r.step(core)
                break
        else:
            pytest.fail("deadlock: no core ready")
    np.testing.assert_array_equal(r.outputs(), ref)


@pytest.mark.parametrize("batch", [1, 2, 3, 4])
def test_multistream_batched_grouping_bit_exact(batch):
    """Frame-level batching x layer pipelining: grouping B frames per
    round (incl. ragged tails) never changes a single output byte."""
    ms, x_q, params, ref = _ms_fixture(n_frames=4)
    y = run_multistream(ms, x_q, params, batch=batch)
    np.testing.assert_array_equal(y, ref, err_msg=f"batch={batch}")


def test_pe_per_core_rides_in_the_streams():
    """Explicit per-core PEConfigs land in each stream's own CFG_PE word,
    change per-core timing, and never change values."""
    specs = block_specs()
    hw = 12
    params = random_chain_params(jax.random.PRNGKey(2), specs, hw)
    pes = [PEConfig(18, 18, 112), PEConfig(3, 3, 14)]
    ms = compile_network(specs, hw, hw, CFUSchedule.FUSED, streams=2,
                         pe_per_core=pes)
    assert ms.meta["pe_per_core"] == pes and ms.meta["hetero"]
    for p, pe in zip(ms.streams, pes):
        assert p.instrs[0].op == "CFG_PE"
        assert p.instrs[0].args == (pe.exp_pes, pe.dw_lanes,
                                    pe.proj_engines)
        assert p.meta["pe"] == pe
    rep = analyze_multistream(ms, "v3")
    # the big core is faster per op than the small core would be: swap
    # the configs and the same segments time differently
    swapped = compile_network(specs, hw, hw, CFUSchedule.FUSED, streams=2,
                              pe_per_core=pes[::-1])
    assert (rep.per_stream[0].total_cycles
            != pytest.approx(
                analyze_multistream(swapped, "v3")
                .per_stream[0].total_cycles))
    rng = np.random.default_rng(0)
    x_q = rng.integers(-128, 128, (2, hw, hw, specs[0][1].cin)) \
        .astype(np.int8)
    homo = compile_network(specs, hw, hw, CFUSchedule.FUSED, streams=2)
    np.testing.assert_array_equal(run_multistream(ms, x_q, params),
                                  run_multistream(homo, x_q, params))


def test_split_pe_budget_exact_and_floored():
    """Budget splits are exact per axis (equal total MACs by construction)
    with a one-engine floor per core."""
    for fracs in ((1.0, 1.0), (1.25, 0.75), (1.5, 1.0, 0.5),
                  (0.5, 0.75, 1.25, 1.5)):
        total = (9 * len(fracs), 9 * len(fracs), 56 * len(fracs))
        pes = split_pe_budget(total, fracs)
        assert sum(p.exp_pes for p in pes) == total[0]
        assert sum(p.dw_lanes for p in pes) == total[1]
        assert sum(p.proj_engines for p in pes) == total[2]
        assert all(p.exp_pes >= 1 and p.dw_lanes >= 1
                   and p.proj_engines >= 1 for p in pes)
    with pytest.raises(ValueError):
        split_pe_budget((2, 9, 56), (1.0, 1.0, 1.0))   # 2 engines, 3 cores


def test_auto_hetero_never_worse_than_homogeneous():
    """The searched allocation space always contains the homogeneous
    split, so the auto-hetero pick's modeled steady-state interval is
    never worse at equal total engine budget."""
    specs = block_specs()
    hw = 24
    base = PEConfig(5, 5, 28)
    for streams in (2, 3):
        cands = hetero_pe_candidates(streams, base)
        assert cands[0] == [base] * streams       # homogeneous is in-space
        homo = compile_network(specs, hw, hw, CFUSchedule.FUSED,
                               pe=base, streams=streams)
        het = compile_network(specs, hw, hw, CFUSchedule.FUSED, pe=base,
                              streams=streams, pe_per_core=AUTO_HETERO)
        pes = het.meta["pe_per_core"]
        assert sum(p.exp_pes for p in pes) == base.exp_pes * streams
        assert sum(p.dw_lanes for p in pes) == base.dw_lanes * streams
        assert sum(p.proj_engines for p in pes) \
            == base.proj_engines * streams
        r_homo = analyze_multistream(homo, "v3")
        r_het = analyze_multistream(het, "v3")
        assert r_het.interval_cycles <= r_homo.interval_cycles * (1 + 1e-9)


def test_timing_batch_amortizes_pipeline_fill():
    """analyze(batch=B): per-frame traffic and iteration compute scale
    with B, the per-phase pipeline fill does not — so per-frame cycles
    fall with batch, approaching the fill-free bound."""
    spec, hw = DSCBlockSpec(cin=8, cmid=48, cout=8, stride=1), 10
    prog = compile_block(spec, hw, hw, CFUSchedule.FUSED)
    r1 = analyze(prog, "v3", batch=1)
    r4 = analyze(prog, "v3", batch=4)
    assert r4.batch == 4
    # weights load once; data traffic scales exactly
    assert r4.weight_bytes == r1.weight_bytes
    assert (r4.dram_bytes - r4.weight_bytes
            == 4 * (r1.dram_bytes - r1.weight_bytes))
    assert r4.macs == 4 * r1.macs
    # fill amortizes: 4 frames in one walk beat 4 independent walks
    assert r4.total_cycles < 4 * r1.total_cycles
    assert r4.frames_per_cycle > r1.frames_per_cycle
    # v1 has no fill -> nothing to amortize, scaling is exact
    s1 = analyze(prog, "v1", batch=1)
    s4 = analyze(prog, "v1", batch=4)
    assert s4.total_cycles == pytest.approx(4 * s1.total_cycles)


def test_multistream_report_throughput_and_energy_per_frame():
    """analyze_multistream reports steady-state frames/cycle and
    energy/frame, and composes fill + rounds for finite frame counts."""
    specs = block_specs()
    ms = compile_network(specs, 12, 12, CFUSchedule.FUSED, streams=2)
    r1 = analyze_multistream(ms, "v3", batch=1)
    r4 = analyze_multistream(ms, "v3", batch=4)
    assert r1.frames_per_cycle == pytest.approx(1 / r1.interval_cycles)
    assert r4.frames_per_cycle == pytest.approx(4 / r4.interval_cycles)
    assert r4.frames_per_cycle > r1.frames_per_cycle   # fill amortized
    assert r4.energy_per_frame_pj == pytest.approx(
        r4.energy_pj["total"] / 4)
    assert r4.energy_per_frame_pj < r1.energy_per_frame_pj
    # 8 frames at batch 4 = 2 rounds through a 2-deep pipeline = 3 rounds
    assert r4.cycles_for_frames(8) == pytest.approx(
        3 * r4.interval_cycles)
    assert r1.cycles_for_frames(1) == pytest.approx(
        2 * r1.interval_cycles)


def test_cfg_dbuf_and_cfg_core_roundtrip():
    """The PR-4 CFG words assemble/disassemble and text-roundtrip like
    every other opcode (the hypothesis layer covers arbitrary operands)."""
    for ins in (isa.Instr("CFG_DBUF", (isa.REG_IN, isa.SPACE_DRAM,
                                       0x123456, 0xABCDEF)),
                isa.Instr("CFG_CORE", (2, 5))):
        assert isa.disassemble(isa.assemble(ins)) == ins
        assert isa.asm_to_instr(isa.instr_to_asm(ins)) == ins


# --- ISA round trips ---------------------------------------------------------


def _canonical_word(op: str, args) -> int:
    """Pack fields per FIELD_SPECS by hand (independent of assemble())."""
    word = isa.OPCODES[op] << 56
    pos = 56
    for v, (_, bits) in zip(args, isa.FIELD_SPECS[op]):
        pos -= bits
        word |= int(v) << pos
    return word


def test_assemble_disassemble_word_roundtrip_every_opcode():
    """assemble(disassemble(w)) == w for canonical words of EVERY opcode
    (incl. CONV_MAC/GAP_*/CFG_PE and the rowtile CFG_STRIP) — the binary
    encoding drops no bits and invents none."""
    rng = np.random.default_rng(7)
    for op, fields in isa.FIELD_SPECS.items():
        for _ in range(16):
            args = tuple(int(rng.integers(0, 1 << bits))
                         for _, bits in fields)
            word = _canonical_word(op, args)
            assert isa.assemble(isa.disassemble(word)) == word, op



def test_every_opcode_roundtrips_through_binary_and_text():
    rng = np.random.default_rng(0)
    for op, fields in isa.FIELD_SPECS.items():
        for _ in range(8):
            args = tuple(int(rng.integers(0, 1 << bits))
                         for _, bits in fields)
            ins = isa.Instr(op, args)
            assert isa.disassemble(isa.assemble(ins)) == ins
            assert isa.asm_to_instr(isa.instr_to_asm(ins)) == ins


@pytest.mark.parametrize("chans", [(320, 1280, 1280),     # the 1x1 head
                                   (1280, 1280, 1000),    # GAP + FC
                                   (4095, 4095, 1023)])   # widest in CFG
def test_cfg_x_carries_channel_counts_wider_than_cfg(chans):
    """A channel count past its CFG field rides in a CFG_X word after the
    CFG; it encodes and decodes round trip, under word parity too. A
    shape that fits emits CFG alone."""
    words = isa.cfg_instrs(*chans, 1, 7, 7)
    assert [w.op for w in words] == (["CFG", "CFG_X"] if max(
        chans[0], chans[2]) > 1023 else ["CFG"])
    prog = isa.Program(words + [isa.Instr("HALT")], meta={"parity": True})
    enc = isa.encode_program(prog)
    assert isa.bad_parity_indices(enc) == []
    dec = isa.decode_words(enc)
    assert dec == prog.instrs
    got = dec[0].args[:3]
    if len(words) == 2:
        got = isa.widen_cfg(*got, dec[1].args)
        flipped = enc.copy()
        flipped[1] ^= np.uint64(1 << 40)      # one bit of the CFG_X word
        assert isa.bad_parity_indices(flipped) == [1]
    assert tuple(got) == chans


def test_vww_program_words_unchanged_by_the_wide_cfg():
    """Programs whose counts fit CFG encode to the same words as before
    CFG_X existed (sha256 of the 80x80 VWW streams, every schedule, and
    the parity-protected fused stream, taken before the change)."""
    import hashlib
    from repro.cfu.compiler import schedule_names
    h = hashlib.sha256()
    for s in schedule_names():
        h.update(isa.encode_program(
            compile_vww_network(block_specs(), 80, s)).tobytes())
    assert h.hexdigest() == ("925ba400e660d5dda8966f4a632c75f7"
                             "1a19a361abf5db399261205f84545fb2")
    prot = isa.encode_program(compile_vww_network(block_specs(), 80,
                                                  "fused", protect=True))
    assert hashlib.sha256(prot.tobytes()).hexdigest() == (
        "269792d617f0e8dbcb44d9360ff794329b77c6cb91e427e4a4447ead90ed8123")


def test_wide_head_network_bit_exact_vs_forward_int8():
    """A 1280-channel head and a 1000-class FC (CFG_X on both units)
    through the golden executor, the cost model and the fast path, held
    to the scalar-core reference logits."""
    from repro.cfu import fastpath
    from repro.models import mobilenetv2 as mnv2
    img_hw = 16
    net = mnv2.init_and_quantize(jax.random.PRNGKey(5), img_hw=img_hw,
                                 head_ch=1280, n_classes=1000)
    params = vww_cfu_params(net)
    rng = np.random.default_rng(5)
    imgs = rng.standard_normal((2, img_hw, img_hw, 3)).astype(np.float32)
    imgs_q = np.asarray(quant.quantize(imgs, net.qp_img))
    ref = np.asarray(mnv2.forward_batch(imgs, net, return_quantized=True))
    prog = compile_vww_network(block_specs(), img_hw, CFUSchedule.FUSED,
                               head_ch=1280, n_classes=1000, protect=True)
    assert sum(i.op == "CFG_X" for i in prog.instrs) == 2
    np.testing.assert_array_equal(run_program(prog, imgs_q, params), ref)
    np.testing.assert_array_equal(fastpath.run_fast(prog, imgs_q, params),
                                  ref)
    # the cost model sees the widened counts: against the 128-channel,
    # 2-class network only the head (1x1 map here) and the FC grow
    wide = analyze(prog).macs_by_engine
    narrow = analyze(compile_vww_network(
        block_specs(), img_hw, CFUSchedule.FUSED)).macs_by_engine
    c_last = block_specs()[-1][1].cout
    assert wide["exp"] - narrow["exp"] == c_last * (1280 - 128)
    assert wide["proj"] - narrow["proj"] == 1280 * 1000 - 128 * 2


def test_compiled_program_roundtrips():
    spec, hw = DSCBlockSpec(cin=8, cmid=48, cout=16, stride=2), 10
    for sched in CFUSchedule:
        prog = compile_block(spec, hw, hw, sched)
        words = isa.encode_program(prog)
        assert isa.decode_words(words) == prog.instrs
        assert (isa.program_from_asm(isa.program_to_asm(prog)).instrs
                == prog.instrs)


def test_field_range_is_enforced():
    with pytest.raises(ValueError):
        isa.Instr("LD_WIN", (1 << 12, 0))       # oy overflows its field
    with pytest.raises(ValueError):
        isa.Instr("EXP_MAC", (0, 1))            # wrong arity
    with pytest.raises(ValueError):
        isa.disassemble(0xFF << 56)             # unknown opcode


def test_mac_without_streamed_weights_faults():
    """LD_WGT's `which` operand is architectural: an engine used before its
    weights were streamed is a program bug the golden model must catch."""
    spec, hw = DSCBlockSpec(cin=6, cmid=18, cout=6, stride=1), 6
    x_q, qp, _ = _block(spec, hw)
    prog = compile_block(spec, hw, hw, CFUSchedule.FUSED)
    bad = [i for i in prog.instrs
           if not (i.op == "LD_WGT" and i.args[0] == isa.WGT_DW)]
    prog.instrs = bad
    with pytest.raises(RuntimeError, match="depthwise engine"):
        run_program(prog, x_q, [qp])


def test_words_alone_plus_meta_reproduce_execution():
    spec, hw = DSCBlockSpec(cin=6, cmid=18, cout=6, stride=1), 6
    x_q, qp, _ = _block(spec, hw)
    prog = compile_block(spec, hw, hw, CFUSchedule.FUSED)
    via_words = run_words(isa.encode_program(prog), x_q, [qp], prog.meta)
    via_prog = run_program(prog, x_q, [qp])
    np.testing.assert_array_equal(via_words, via_prog)


# --- timing model vs the analytic models ------------------------------------

MOBILENET_CHAIN_HW = [40, 40, 20, 20, 10, 10, 5]  # input hw of each block


@pytest.mark.parametrize("bi", range(len(MOBILENET_CHAIN_HW)))
def test_traffic_matches_analytic_for_all_mobilenet_blocks(bi):
    (name, spec), hw = block_specs()[bi], MOBILENET_CHAIN_HW[bi]
    t = block_traffic(spec, hw, hw, name)
    rep_d = analyze(compile_block(spec, hw, hw, CFUSchedule.LAYER_DRAM))
    rep_s = analyze(compile_block(spec, hw, hw, CFUSchedule.LAYER_SRAM))
    rep_f = analyze(compile_block(spec, hw, hw, CFUSchedule.FUSED))
    # Exact equality with the paper's Eq. 1/2 byte counts, not approximate.
    assert rep_d.dram_bytes == t.baseline_total
    assert rep_d.sram_bytes == 0
    assert rep_s.dram_bytes == t.baseline_total - t.intermediate_bytes
    assert rep_s.sram_bytes == t.intermediate_bytes
    assert rep_f.dram_bytes == t.fused_total
    assert rep_f.sram_bytes == 0
    # The fused pipeline needs NO scratch; the SRAM schedule needs at least
    # the paper's Eq. 2 buffer.
    assert rep_f.sram_buffer_bytes == 0
    assert rep_s.sram_buffer_bytes >= min_sram_buffer_bytes(spec, hw, hw)


@pytest.mark.parametrize("spec,hw", [
    (DSCBlockSpec(cin=32, cmid=32, cout=16, stride=1), 28),   # MNV2 first
    (DSCBlockSpec(cin=8, cmid=8, cout=8, stride=2), 9),
])
def test_traffic_matches_analytic_without_expansion(spec, hw):
    """t=1: the streams load no EXP weights and count no expansion MACs,
    and their bytes equal Eq. 1/2 with no F1 (only F2 is materialized)."""
    t = block_traffic(spec, hw, hw)
    h2, w2 = spec.out_hw(hw, hw)
    assert t.intermediate_bytes == 2 * h2 * w2 * spec.cmid
    reps = {s: analyze(compile_block(spec, hw, hw, s))
            for s in (CFUSchedule.LAYER_DRAM, CFUSchedule.LAYER_SRAM,
                      CFUSchedule.FUSED)}
    for rep in reps.values():
        assert "exp" not in rep.macs_by_engine
        assert "EXP_MAC" not in rep.retired
        assert rep.weight_bytes == 9 * spec.cmid + spec.cmid * spec.cout
    assert reps[CFUSchedule.LAYER_DRAM].dram_bytes == t.baseline_total
    assert reps[CFUSchedule.LAYER_SRAM].dram_bytes == \
        t.baseline_total - t.intermediate_bytes
    assert reps[CFUSchedule.LAYER_SRAM].sram_bytes == t.intermediate_bytes
    assert reps[CFUSchedule.LAYER_SRAM].sram_buffer_bytes == \
        min_sram_buffer_bytes(spec, hw, hw)
    assert reps[CFUSchedule.FUSED].dram_bytes == t.fused_total


def test_cycles_match_calibrated_fusion_model():
    """The stream-derived cycles equal core.fusion's closed-form model."""
    spec, hw = DSCBlockSpec(cin=8, cmid=48, cout=8, stride=1), 40
    prog = compile_block(spec, hw, hw, CFUSchedule.FUSED)
    for pl, sched in (("v1", Schedule.V1_PIXEL_SEQUENTIAL),
                      ("v2", Schedule.V2_INTER_STAGE),
                      ("v3", Schedule.V3_INTRA_STAGE)):
        got = analyze(prog, pl).total_cycles
        want = modeled_cycles(spec, hw, hw, sched)
        assert got == pytest.approx(want, rel=1e-6), pl


def test_fused_speedup_reproduces_paper_block3():
    """59.3x (paper Table III(A), 3rd layer) within the model's tolerance."""
    spec, hw = DSCBlockSpec(cin=8, cmid=48, cout=8, stride=1), 40
    sw = modeled_cycles(spec, hw, hw, Schedule.V0_LAYER_BY_LAYER)
    rep3 = analyze(compile_block(spec, hw, hw, CFUSchedule.FUSED), "v3")
    assert 50.0 < sw / rep3.total_cycles < 70.0
    # and the fused stream beats both layer-by-layer CFU schedules
    ld = analyze(compile_block(spec, hw, hw, CFUSchedule.LAYER_DRAM), "v3")
    ls = analyze(compile_block(spec, hw, hw, CFUSchedule.LAYER_SRAM), "v3")
    assert rep3.total_cycles < ls.total_cycles < ld.total_cycles


def test_fused_energy_accounts_for_recompute():
    """The fused MAC count honestly includes the 9x expansion recompute."""
    spec, hw = DSCBlockSpec(cin=8, cmid=48, cout=8, stride=1), 10
    f = analyze(compile_block(spec, hw, hw, CFUSchedule.FUSED))
    d = analyze(compile_block(spec, hw, hw, CFUSchedule.LAYER_DRAM))
    assert d.macs == sum(spec.macs(hw, hw).values())
    assert f.macs > d.macs                      # No-Local-Reuse trade
    # ... and still wins on total energy: movement dominates MACs.
    assert f.energy_pj["total"] < d.energy_pj["total"]


# --- multi-PE timing ---------------------------------------------------------


def test_pe_scaling_monotone_and_default_exact():
    """Default PEConfig reproduces the calibrated model exactly; fewer
    engines never get faster, more never get slower, and the gain
    saturates (requant units don't scale — the sweep's knee)."""
    spec, hw = DSCBlockSpec(cin=8, cmid=48, cout=8, stride=1), 12
    prog = compile_block(spec, hw, hw, CFUSchedule.FUSED)
    base = analyze(prog, "v3").total_cycles
    assert base == analyze(prog, "v3", pe=PEConfig(9, 9, 56)).total_cycles
    cyc = [analyze(prog, "v3", pe=PEConfig(e, e, p)).total_cycles
           for e, p in ((3, 14), (6, 28), (9, 56), (18, 112), (36, 224))]
    assert all(a >= b for a, b in zip(cyc, cyc[1:]))      # monotone
    assert cyc[0] > base                                  # fewer PEs: slower
    # diminishing returns: the last doubling buys less than the first
    assert (cyc[0] - cyc[1]) > (cyc[3] - cyc[4])


def test_cfg_pe_rides_in_the_stream():
    """The engine counts are program state: a stream compiled for a bigger
    array times differently with NO analyze() override, and the word
    round-trips like any other."""
    spec, hw = DSCBlockSpec(cin=8, cmid=48, cout=8, stride=1), 10
    small = compile_block(spec, hw, hw, CFUSchedule.FUSED,
                          pe=PEConfig(3, 3, 14))
    big = compile_block(spec, hw, hw, CFUSchedule.FUSED,
                        pe=PEConfig(18, 18, 112))
    assert small.instrs[0].op == "CFG_PE"
    assert analyze(small, "v3").total_cycles > analyze(big, "v3").total_cycles
    # ...and the executor's results are unaffected by engine counts.
    x_q, qp, ref = _block(spec, hw)
    np.testing.assert_array_equal(run_program(small, x_q, [qp]),
                                  run_program(big, x_q, [qp]))


# --- golden-vector regression (full VWW inference) ---------------------------

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "golden",
                           "cfu_vww.json")


def _vww_golden_actual():
    """Recompute every golden quantity for the canonical VWW inference
    (seed-0 network, seed-0 image, 80x80)."""
    from repro.models import mobilenetv2 as mnv2
    net = mnv2.init_and_quantize(jax.random.PRNGKey(0), img_hw=80)
    net_specs = mnv2.block_specs()
    params = vww_cfu_params(net)
    progs = {s: compile_vww_network(net_specs, 80, s) for s in CFUSchedule}
    fused = progs[CFUSchedule.FUSED]
    reps = {pl: analyze(fused, pl) for pl in ("v1", "v2", "v3")}
    ld = analyze(progs[CFUSchedule.LAYER_DRAM], "v1")
    ls = analyze(progs[CFUSchedule.LAYER_SRAM], "v1")
    rng = np.random.default_rng(0)
    img = rng.standard_normal((80, 80, 3)).astype(np.float32)
    img_q = np.asarray(quant.quantize(img, net.qp_img))
    logits = run_program(fused, img_q, params)
    # heterogeneous 2-core frame pipeline: FIXED tail-heavy allocation of
    # the 2x-paper engine budget (deterministic, independent of the
    # auto-hetero search so cost-model tuning can't silently move it)
    het_pes = split_pe_budget((18, 18, 112), (0.75, 1.25))
    ms = compile_vww_network(net_specs, 80, CFUSchedule.FUSED, streams=2,
                             pe_per_core=het_pes)
    ms_rep = analyze_multistream(ms, "v3")
    ms_rep4 = analyze_multistream(ms, "v3", batch=4)
    ms_logits = run_multistream(ms, img_q, params)
    return {
        "img_hw": 80,
        "fused": {
            "n_instr": len(fused),
            "cycles": {pl: reps[pl].total_cycles for pl in reps},
            "dram_bytes": reps["v3"].dram_bytes,
            "sram_bytes": reps["v3"].sram_bytes,
            "weight_bytes": reps["v3"].weight_bytes,
            "macs": reps["v3"].macs,
        },
        "layer_dram": {"n_instr": len(progs[CFUSchedule.LAYER_DRAM]),
                       "cycles": ld.total_cycles,
                       "dram_bytes": ld.dram_bytes},
        "layer_sram": {"cycles": ls.total_cycles,
                       "dram_bytes": ls.dram_bytes,
                       "sram_bytes": ls.sram_bytes,
                       "sram_buffer_bytes": ls.sram_buffer_bytes},
        "logits_q": np.asarray(logits).astype(int).tolist(),
        "multistream_hetero_2core": {
            "pe_per_core": [[p.exp_pes, p.dw_lanes, p.proj_engines]
                            for p in het_pes],
            "partition": ms.meta["partition"],
            "interval_cycles_v3": ms_rep.interval_cycles,
            "handoff_cycles": ms_rep.handoff_cycles,
            "dram_bytes": ms_rep.dram_bytes,
            "frames_per_cycle_b4": ms_rep4.frames_per_cycle,
            "logits_q": np.asarray(ms_logits).astype(int).tolist(),
        },
    }


def test_vww_golden_vectors():
    """Byte/cycle/logit totals of one full VWW inference are pinned to
    checked-in golden values, so timing-model or executor refactors cannot
    silently drift from the Table III/VI-calibrated behaviour.

    Regenerate (after an INTENTIONAL model change, with the diff reviewed):
        REGEN_GOLDEN=1 PYTHONPATH=src python -m pytest \
            tests/test_cfu.py -k golden
    """
    got = _vww_golden_actual()
    if os.environ.get("REGEN_GOLDEN"):
        os.makedirs(os.path.dirname(GOLDEN_PATH), exist_ok=True)
        with open(GOLDEN_PATH, "w") as f:
            json.dump(got, f, indent=2, sort_keys=True)
        pytest.skip(f"regenerated {GOLDEN_PATH}")
    with open(GOLDEN_PATH) as f:
        want = json.load(f)
    # integer quantities: exact; cycles: floats summed in a fixed order,
    # compared tight enough that any real model change trips the test.
    assert got["logits_q"] == want["logits_q"]
    for sched in ("fused", "layer_dram", "layer_sram"):
        for key, val in want[sched].items():
            if key == "cycles":
                continue
            assert got[sched][key] == val, (sched, key)
    for pl, cyc in want["fused"]["cycles"].items():
        assert got["fused"]["cycles"][pl] == pytest.approx(cyc, rel=1e-9), pl
    assert got["layer_dram"]["cycles"] == pytest.approx(
        want["layer_dram"]["cycles"], rel=1e-9)
    assert got["layer_sram"]["cycles"] == pytest.approx(
        want["layer_sram"]["cycles"], rel=1e-9)
    ms_got, ms_want = (got["multistream_hetero_2core"],
                       want["multistream_hetero_2core"])
    for key, val in ms_want.items():
        if key in ("interval_cycles_v3", "frames_per_cycle_b4"):
            assert ms_got[key] == pytest.approx(val, rel=1e-9), key
        else:
            assert ms_got[key] == val, key


# The PR-3 fingerprint of the homogeneous streams=1 goldens. The golden
# FILE may grow new sections (REGEN_GOLDEN), but these literals must stay
# byte-identical — they anchor the Table III(A)-calibrated model (the
# 27.4x/46.3x/59.3x progression rides on the fused v1/v2/v3 cycles).
_PR3_GOLDEN_FINGERPRINT = {
    ("fused", "cycles", "v1"): 12651351.200000323,
    ("fused", "cycles", "v2"): 9442754.400000235,
    ("fused", "cycles", "v3"): 8559034.400000181,
    ("fused", "dram_bytes"): 221346,
    ("fused", "macs"): 26788256,
    ("fused", "n_instr"): 29946,
    ("layer_dram", "cycles"): 46357051.19999898,
    ("layer_dram", "dram_bytes"): 1097346,
    ("layer_sram", "cycles"): 10430861.200000247,
    ("layer_sram", "dram_bytes"): 221346,
    # re-pinned from [-90, -93] when JAX's default random stream moved
    # (jax_threefry_partitionable): the network's f32 weights changed,
    # not the arithmetic
    ("logits_q",): [-61, -128],
}


def test_golden_streams1_byte_identical_to_pr3():
    """Regression gate for the REGEN_GOLDEN flow itself: whatever new
    sections land in the golden file, the homogeneous streams=1 entries
    must remain exactly the PR-3 values."""
    with open(GOLDEN_PATH) as f:
        want = json.load(f)
    for path, val in _PR3_GOLDEN_FINGERPRINT.items():
        node = want
        for k in path:
            node = node[k]
        assert node == val, path
