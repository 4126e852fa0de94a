"""The jitted fast path is a twin of the word interpreter, never a fork.

``cfu/fastpath.py`` lifts a compiled program from its encoded words into
one jitted, vmapped XLA computation, cached by program fingerprint.
These tests pin the whole contract:

* the DIFFERENTIAL MATRIX — every registered schedule (plus ``auto``) x
  streams {1, 2} x batch {1, 3} (3 frames over group-2 rounds is the
  ragged multistream tail) — asserts exact integer equality between the
  fast path and ``run_words`` / ``run_multistream``, on a prime feature
  size so rowtile halos and ragged Pallas tiles are exercised;
* CACHE CORRECTNESS — recompiling the same program hits the cache with
  the SAME traced executor; changing the PE config, the schedule, or the
  quantization constants moves the key and re-traces (no stale constants);
  changing only the weight VALUES reuses the trace and still changes the
  output (weights are traced arguments, not baked); the cache is a
  bounded LRU — evictions happen oldest-use-first and an evicted program
  re-traces bit-exactly;
* the spot checker's ``backend="fast"`` mode stays anchored: the sampled
  golden cross-check still catches a fast-vs-golden divergence.

Exactness discipline matches the rest of the repo: assert_array_equal,
never allclose — int8 inference has no tolerance budget.
"""

import dataclasses
import functools

import jax
import numpy as np
import pytest

from repro.cfu import fastpath
from repro.cfu.compiler import compile_network, schedule_names
from repro.cfu.executor import run_multistream, run_program
from repro.cfu.timing import PEConfig
from repro.core import dsc, quant
from repro.core.dsc import DSCBlockSpec

HW = 13                       # prime: every tile/halo edge case is live
CHAIN = (DSCBlockSpec(cin=3, cmid=9, cout=5, stride=1),
         DSCBlockSpec(cin=5, cmid=15, cout=5, stride=2),
         DSCBlockSpec(cin=5, cmid=10, cout=4, stride=1))


@functools.lru_cache(maxsize=None)
def _chain_fixture(seed: int = 0):
    params, h = [], HW
    for i, spec in enumerate(CHAIN):
        p32 = dsc.init_dsc_block_f32(jax.random.PRNGKey(seed + i), spec)
        calib = np.asarray(jax.random.normal(
            jax.random.PRNGKey(seed + 100 + i), (h, h, spec.cin)))
        params.append(dsc.quantize_dsc_block(p32, spec, calib))
        h, _ = spec.out_hw(h, h)
    specs = [(f"b{i}", s) for i, s in enumerate(CHAIN)]
    rng = np.random.default_rng(seed)
    x_f = rng.standard_normal((3, HW, HW, CHAIN[0].cin)).astype(np.float32)
    x_q = np.asarray(quant.quantize(x_f, params[0].qp_in))
    return specs, params, x_q


def setup_module(module):
    fastpath.clear_cache()


# --- the differential matrix ------------------------------------------------


MATRIX = [(s, n, b) for s in schedule_names(include_auto=True)
          for n in (1, 2) for b in (1, 3)]


@pytest.mark.parametrize("sched,streams,batch", MATRIX)
def test_matrix_fast_equals_interpreter(sched, streams, batch):
    specs, params, x_q = _chain_fixture()
    prog = compile_network(specs, HW, HW, sched, streams=streams)
    x = x_q[:batch] if batch > 1 else x_q[0]
    if streams == 1:
        ref = run_program(prog, x, params)
    else:
        # group size 2 over 3 frames = ragged final round in the runner
        ref = run_multistream(prog, x, params, batch=2)
    got = fastpath.run_fast(prog, x, params)
    np.testing.assert_array_equal(
        got, ref, err_msg=f"{sched} streams={streams} batch={batch}")


def test_matrix_vww_network_fast_equals_interpreter():
    """Whole VWW inference (stem + chain + head + GAP + FC): the lifted
    aux stages, not just DSC blocks."""
    from repro.cfu.compiler import compile_vww_network
    from repro.cfu.network import vww_cfu_params
    from repro.models import mobilenetv2 as mnv2
    hw = 16
    net = mnv2.init_and_quantize(jax.random.PRNGKey(2), img_hw=hw)
    params = vww_cfu_params(net)
    rng = np.random.default_rng(7)
    imgs = rng.standard_normal((3, hw, hw, 3)).astype(np.float32)
    x_q = np.asarray(quant.quantize(imgs, net.qp_img))
    for streams in (1, 2):
        prog = compile_vww_network(mnv2.block_specs(), hw, "fused-rowtile",
                                   streams=streams)
        ref = (run_program(prog, x_q, params) if streams == 1
               else run_multistream(prog, x_q, params, batch=2))
        got = fastpath.run_fast(prog, x_q, params)
        np.testing.assert_array_equal(got, ref,
                                      err_msg=f"vww streams={streams}")
        got1 = fastpath.run_fast(prog, x_q[0], params)
        np.testing.assert_array_equal(got1, ref[0],
                                      err_msg=f"vww single frame")


# --- blocks without expansion (t=1) ------------------------------------------

T1_CHAIN = (DSCBlockSpec(cin=5, cmid=5, cout=5, stride=1),    # residual
            DSCBlockSpec(cin=5, cmid=15, cout=6, stride=2),
            DSCBlockSpec(cin=6, cmid=6, cout=4, stride=2))    # odd map


@functools.lru_cache(maxsize=None)
def _t1_fixture():
    from repro.cfu.network import random_chain_params
    specs = [(f"t{i}", s) for i, s in enumerate(T1_CHAIN)]
    params = random_chain_params(jax.random.PRNGKey(4), specs, HW, seed=4)
    rng = np.random.default_rng(4)
    x_q = rng.integers(-128, 128, (3, HW, HW, 5), dtype=np.int8)
    want = x_q
    for p in params:
        want = np.asarray(jax.vmap(
            functools.partial(dsc.dsc_block_reference, p=p))(want))
    return specs, params, x_q, want


@pytest.mark.parametrize("sched", schedule_names(include_auto=True))
def test_t1_chain_every_path_equals_reference(sched):
    """A chain with blocks without expansion: the compiler's stream on the
    golden executor and the fast path (and, under fused, the Pallas
    bodies) all equal the layer-by-layer reference; strip schedules send
    those blocks to fused and say so in the meta."""
    specs, params, x_q, want = _t1_fixture()
    prog = compile_network(specs, HW, HW, sched)
    np.testing.assert_array_equal(run_program(prog, x_q, params), want)
    np.testing.assert_array_equal(fastpath.run_fast(prog, x_q, params),
                                  want)
    kinds = [st.kind for st in fastpath.fast_executor(prog, params).stages]
    assert kinds == ["dw", "dsc", "dw"]
    if sched in ("fused-rowtile", "fused-winograd"):
        assert set(prog.meta["rerouted"]) >= {"t0", "t2"}
        assert all(prog.meta["block_schedules"][n] == "fused"
                   for n in ("t0", "t2"))
    if sched == "fused":
        got = fastpath.run_fast(prog, x_q, params, use_pallas=True)
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("sched", schedule_names(include_auto=True))
def test_dsc_stage_body_choice(sched):
    """A DSC stage lowers to a ``pallas_call`` exactly when kernel bodies
    are asked for (``use_pallas``) and its block is lowered fused or
    rowtile; layer-dram, layer-sram and fused-winograd blocks run the
    reference body, and with ``use_pallas=False`` no stage holds a
    kernel. Both chains: with and without expansion."""
    for specs, params, *_ in (_chain_fixture(), _t1_fixture()):
        prog = compile_network(specs, HW, HW, sched)
        for st in fastpath._lift_program(prog):
            p = params[st.block]
            x = np.zeros((st.h, st.w, st.cin), np.int8)
            w = {k: getattr(p, k) for k in fastpath._STAGE_ARRAYS[st.kind]}
            lowered = prog.meta["block_schedules"][specs[st.block][0]]
            for use_pallas in (False, True):
                fn = fastpath._build_stage_fn(st, p, use_pallas)
                jaxpr = str(jax.make_jaxpr(fn)(x, w))
                assert ("pallas_call" in jaxpr) == (
                    use_pallas and lowered in ("fused", "fused-rowtile")), \
                    f"{sched}: {lowered} block {st.block}, {use_pallas=}"


@pytest.mark.parametrize("hw,cout", [(80, 8), (224, 32)],
                         ids=["80x80x3-8", "224x224x3-32"])
def test_stem_stage_bit_exact_vs_conv_stem(hw, cout):
    """The stem stage lifted from the VWW or the MobileNetV2 1.0 program
    (im2col taps, one GEMM over the free (H2, W2) dimensions, requant
    behind it) equals the model's ``lax.conv`` stem bit for bit, with
    outputs on both clamps: the zero point (ReLU) and the ReLU6 cap."""
    import json
    import pathlib
    import types

    from repro.cfu.compiler import compile_vww_network
    from repro.cfu.network import CFUStemParams
    from repro.models import mobilenetv2 as mnv2

    if hw == 80:
        specs = mnv2.block_specs()
    else:
        cfg = json.loads((pathlib.Path(__file__).resolve().parents[1] /
                          "chipbench/configs/mnv2-224-fused.json").read_text())
        specs = [(name, DSCBlockSpec(ci, cm, co, s))
                 for name, ci, cm, co, s in cfg["blocks"]]
    prog = compile_vww_network(specs, hw, "fused")
    stage = fastpath._lift_program(prog)[0]
    assert (stage.kind, stage.cin, stage.cout, stage.stride) == (
        "stem", 3, cout, 2)

    rng = np.random.default_rng(hw)
    qp_img, qp_stem = quant.QParams(1 / 128, 3), quant.QParams(0.05, -20)
    q6 = quant.relu6_max_q(qp_stem)
    p = CFUStemParams(
        rng.integers(-128, 128, (3, 3, 3, cout), dtype=np.int8),
        rng.integers(-5000, 5000, cout, dtype=np.int32),
        rng.uniform(1e-3, 1e-2, cout).astype(np.float32),
        qp_img, qp_stem, q6)
    imgs = rng.integers(-128, 128, (2, hw, hw, 3), dtype=np.int8)
    w = {k: getattr(p, k) for k in fastpath._STAGE_ARRAYS["stem"]}
    got = np.asarray(jax.vmap(
        fastpath._build_stage_fn(stage, p, use_pallas=False),
        in_axes=(0, None))(imgs, w))

    ref_p = types.SimpleNamespace(stem_w=p.w_conv, stem_b=p.b_conv,
                                  stem_m=p.m_exp, qp_img=qp_img,
                                  qp_stem=qp_stem)
    want = np.asarray(jax.vmap(lambda im: mnv2._stem_int8(im, ref_p))(imgs))
    assert got.shape == (2, -(-hw // 2), -(-hw // 2), cout)
    np.testing.assert_array_equal(got, want)
    assert (want == qp_stem.zero_point).any() and (want == q6).any()
    assert ((want > qp_stem.zero_point) & (want < q6)).any()


# --- fingerprints + cache ---------------------------------------------------


def test_fingerprint_deterministic_and_schedule_sensitive():
    specs, params, _ = _chain_fixture()
    fp = {s: fastpath.program_fingerprint(
        compile_network(specs, HW, HW, s)) for s in schedule_names()}
    # recompiling is byte-stable
    assert fp["fused"] == fastpath.program_fingerprint(
        compile_network(specs, HW, HW, "fused"))
    # distinct schedules are distinct programs
    assert len(set(fp.values())) == len(fp)


def test_fingerprint_sensitive_to_pe_and_geometry():
    specs, params, _ = _chain_fixture()
    base = fastpath.program_fingerprint(
        compile_network(specs, HW, HW, "fused"))
    pe = fastpath.program_fingerprint(
        compile_network(specs, HW, HW, "fused", pe=PEConfig(4, 4, 21)))
    geom = fastpath.program_fingerprint(
        compile_network(specs, 12, 12, "fused"))
    assert len({base, pe, geom}) == 3


def test_cache_hit_same_program_miss_on_change():
    fastpath.clear_cache()
    specs, params, x_q = _chain_fixture()
    prog_a = compile_network(specs, HW, HW, "fused")
    prog_b = compile_network(specs, HW, HW, "fused")        # recompiled
    ex_a = fastpath.fast_executor(prog_a, params)
    ex_b = fastpath.fast_executor(prog_b, params)
    assert ex_a is ex_b                     # same fingerprint, same trace
    info = fastpath.cache_info()
    assert info["hits"] == 1 and info["misses"] == 1
    # changed PE config / schedule: different fingerprint, fresh executor
    ex_pe = fastpath.fast_executor(
        compile_network(specs, HW, HW, "fused", pe=PEConfig(4, 4, 21)),
        params)
    ex_sched = fastpath.fast_executor(
        compile_network(specs, HW, HW, "layer-dram"), params)
    assert ex_pe is not ex_a and ex_sched is not ex_a
    assert fastpath.cache_info()["misses"] == 3


def test_cache_misses_on_changed_quant_constants():
    """Same program, recalibrated params: the static key moves, so the
    trace is rebuilt with the NEW constants — and both stay bit-exact."""
    specs, params, x_q = _chain_fixture()
    specs2, params2, x_q2 = _chain_fixture(seed=11)
    prog = compile_network(specs, HW, HW, "fused")
    ex1 = fastpath.fast_executor(prog, params)
    ex2 = fastpath.fast_executor(prog, params2)
    assert ex1 is not ex2                   # no stale constants
    np.testing.assert_array_equal(fastpath.run_fast(prog, x_q, params),
                                  run_program(prog, x_q, params))
    np.testing.assert_array_equal(fastpath.run_fast(prog, x_q2, params2),
                                  run_program(prog, x_q2, params2))


def test_weights_are_traced_not_baked():
    """Perturbing only weight VALUES (same quant domains) must reuse the
    cached trace and still change the output."""
    specs, params, x_q = _chain_fixture()
    prog = compile_network(specs, HW, HW, "fused")
    ex = fastpath.fast_executor(prog, params)
    w2 = np.array(params[0].w_exp)
    w2[0, 0] = np.int8(w2[0, 0] + 1 if w2[0, 0] < 127 else w2[0, 0] - 1)
    params_w = [dataclasses.replace(params[0], w_exp=w2)] + params[1:]
    assert fastpath.fast_executor(prog, params_w) is ex   # shared trace
    y_ref = run_program(prog, x_q, params_w)
    np.testing.assert_array_equal(fastpath.run_fast(prog, x_q, params_w),
                                  y_ref)
    assert not np.array_equal(y_ref, run_program(prog, x_q, params))


def test_forced_pallas_stage_bodies_bit_exact_and_separate_cache_key():
    """On CPU the default trace runs the reference body; forcing
    ``use_pallas=True`` must lift through the Pallas kernels instead,
    stay bit-exact against the interpreter (fused AND rowtile lowerings),
    and occupy its own cache slot (the backend is part of the key)."""
    specs, params, x_q = _chain_fixture()
    for sched in ("fused", "fused-rowtile"):
        prog = compile_network(specs, HW, HW, sched)
        ex_ref = fastpath.fast_executor(prog, params)
        ex_pl = fastpath.fast_executor(prog, params, use_pallas=True)
        assert ex_pl is not ex_ref and ex_pl.use_pallas
        np.testing.assert_array_equal(
            fastpath.run_fast(prog, x_q, params, use_pallas=True),
            run_program(prog, x_q, params), err_msg=sched)
        # forcing again hits the pallas-keyed cache entry
        assert fastpath.fast_executor(prog, params,
                                      use_pallas=True) is ex_pl


def test_cache_lru_eviction_and_bit_exact_retrace():
    """Capping the trace cache evicts in least-recently-used order; an
    evicted program re-traces on its next request (a fresh miss), and the
    re-trace stays bit-exact against the interpreter."""
    fastpath.clear_cache()
    specs, params, x_q = _chain_fixture()
    progs = [compile_network(specs, HW, HW, s)
             for s in ("fused", "fused-rowtile", "fused-winograd")]
    try:
        fastpath.set_cache_limit(2)
        ex0 = fastpath.fast_executor(progs[0], params)
        ex1 = fastpath.fast_executor(progs[1], params)
        assert fastpath.cache_info()["size"] == 2
        assert fastpath.cache_info()["evictions"] == 0
        # touching prog0 makes prog1 the LRU entry; prog2 then evicts it
        assert fastpath.fast_executor(progs[0], params) is ex0
        fastpath.fast_executor(progs[2], params)
        info = fastpath.cache_info()
        assert info["size"] == 2 and info["evictions"] == 1
        assert fastpath.fast_executor(progs[0], params) is ex0  # survived
        # prog1 was evicted: the next request is a miss that re-traces...
        misses = fastpath.cache_info()["misses"]
        ex1b = fastpath.fast_executor(progs[1], params)
        assert ex1b is not ex1
        assert fastpath.cache_info()["misses"] == misses + 1
        # ...and the fresh trace is still bit-exact
        np.testing.assert_array_equal(
            fastpath.run_fast(progs[1], x_q, params),
            run_program(progs[1], x_q, params))
        # shrinking below the live size evicts immediately
        fastpath.set_cache_limit(1)
        assert fastpath.cache_info()["size"] == 1
        with pytest.raises(ValueError):
            fastpath.set_cache_limit(0)
    finally:
        fastpath.clear_cache()          # also restores the default limit
    assert fastpath.cache_info()["limit"] == fastpath._DEFAULT_CACHE_LIMIT


# --- the stage arrays stay on the device between calls -----------------------


def _stage_arrays_as(params, kind):
    """The chain's params with every stage array as ``kind``: the
    fixture's own device arrays, host copies, or device arrays whose
    weights are int32 instead of the stage's int8."""
    def conv(name, v):
        if kind == "host":
            return np.array(v)
        if kind == "wrong_dtype" and name.startswith("w"):
            return jax.numpy.asarray(v, jax.numpy.int32)
        return v
    return [dataclasses.replace(p, **{n: conv(n, getattr(p, n))
                                      for n in fastpath._STAGE_ARRAYS["dsc"]})
            for p in params]


def _one_step_off(a, index):
    """A copy of int8 ``a`` with one element one step away."""
    a = np.array(a)
    a[index] = a[index] + 1 if a[index] < 127 else a[index] - 1
    return a


# arrays the first call puts on the device, by what the caller passes:
# device arrays of the stage's dtype none, host arrays all, int32 device
# weights their three per block (converted on the device)
RESIDENT = {"device": 0, "host": 9 * len(CHAIN), "wrong_dtype": 3 * len(CHAIN)}


@pytest.mark.parametrize("kind", sorted(RESIDENT))
def test_stage_arrays_bound_once_then_reused(kind):
    specs, params, x_q = _chain_fixture()
    params = _stage_arrays_as(params, kind)
    prog = compile_network(specs, HW, HW, "fused")
    ex = fastpath.FastPathExecutor(prog, params)
    passed, jitted = [], ex.jitted

    def spy(x, wlist):
        passed.append(wlist)
        return jitted(x, wlist)
    ex.jitted = spy
    ref = run_program(prog, x_q, params)
    for _ in range(2):
        np.testing.assert_array_equal(ex(x_q, params), ref)
    # the second call uploads and converts nothing
    assert ex.weight_uploads == RESIDENT[kind]
    assert ex.weight_binds == int(RESIDENT[kind] > 0)
    first, second = passed
    for st, w1, w2 in zip(ex.stages, first, second):
        for name, a in w1.items():
            src = getattr(params[st.block], name)
            assert isinstance(a, jax.Array) and a.devices() == {ex.device}
            assert a.dtype == fastpath._DTYPES[name[0]], name
            # a device array of the stage's dtype reaches the chain as the
            # caller's own object: never copied back, never uploaded
            assert (a is src) == (isinstance(src, jax.Array)
                                  and src.dtype == a.dtype), name
            assert w2[name] is a, name


def test_host_stage_array_changed_in_place_is_uploaded_again():
    specs, params, x_q = _chain_fixture()
    params = _stage_arrays_as(params, "host")
    prog = compile_network(specs, HW, HW, "fused")
    ex = fastpath.FastPathExecutor(prog, params)
    y0 = ex(x_q, params)
    np.testing.assert_array_equal(y0, run_program(prog, x_q, params))
    uploads = ex.weight_uploads
    params[0].w_exp[...] = _one_step_off(params[0].w_exp, (0, 0))
    ref = run_program(prog, x_q, params)
    assert not np.array_equal(ref, y0)
    np.testing.assert_array_equal(ex(x_q, params), ref)
    assert ex.weight_uploads == uploads + 1         # that array alone
    # a fresh params list with equal contents reuses every upload
    np.testing.assert_array_equal(
        ex(x_q, _stage_arrays_as(params, "host")), ref)
    assert ex.weight_uploads == uploads + 1


def test_alternating_weight_sets_on_a_shared_executor():
    """Two weight sets of the same quantization constants share one
    executor: each switch re-binds what differs and stays bit-exact."""
    specs, params, x_q = _chain_fixture()
    prog = compile_network(specs, HW, HW, "fused")
    a = _stage_arrays_as(params, "host")
    b = [dataclasses.replace(a[0], w_exp=_one_step_off(a[0].w_exp, (0, 0)))
         ] + a[1:]
    ex = fastpath.fast_executor(prog, a)
    assert fastpath.fast_executor(prog, b) is ex
    refs = [run_program(prog, x_q, p) for p in (a, b)]
    assert not np.array_equal(*refs)
    uploads = []
    for i in (0, 1, 0):
        np.testing.assert_array_equal(ex(x_q, (a, b)[i]), refs[i])
        uploads.append(ex.weight_uploads)
    assert np.diff(uploads).tolist() == [1, 1]    # the one array that differs


def test_run_fast_rejects_bad_input_shape():
    specs, params, _ = _chain_fixture()
    prog = compile_network(specs, HW, HW, "fused")
    with pytest.raises(ValueError):
        fastpath.run_fast(prog, np.zeros((HW, HW), np.int8), params)


# --- the chunk plan of a large call ------------------------------------------

IMG80, IMG224 = 80 * 80 * 3, 224 * 224 * 3


@pytest.mark.parametrize("n, image_bytes, k", [
    (256, IMG80, 1),          # VWW batch, 4.9 MB: one put, one dispatch
    (256, IMG224, 2),         # 38.5 MB: two chunks of 128 images, 19.3 MB
    (1, IMG224, 1),           # a single frame
    (8, IMG224, 1),           # a spot-check batch
    (250, IMG224, 1),         # no divisor holds a multiple of 128 images
    (384, IMG224, 3),         # three of 128
    (1024, IMG224, 8),        # eight of 128
    (2048, IMG80, 4),         # 512 images of 80x80 to pass the byte floor
])
def test_chunk_plan(n, image_bytes, k):
    got = fastpath._chunk_count(n, image_bytes)
    assert got == k
    assert n % got == 0

    def fits(j):              # j chunks of whole lanes, each over the floor
        m = n // j
        return (n % j == 0 and m % fastpath._CHUNK_IMAGES == 0
                and m * image_bytes >= fastpath._CHUNK_MIN_BYTES)
    assert got == 1 or fits(got)
    assert not any(fits(j) for j in range(got + 1, n + 1))


# --- the fast spot-check backend stays anchored ------------------------------


def test_fast_spot_check_backend_cross_checks_golden():
    from repro.cfu.serve.check import DifferentialSpotCheck
    specs, params, x_q = _chain_fixture()
    prog = compile_network(specs, HW, HW, "fused")

    def sample(rng, n):
        frames = x_q[rng.integers(0, x_q.shape[0], size=n)]
        return frames, run_program(prog, frames, params)

    spot = DifferentialSpotCheck(prog, params, sample, every=1,
                                 max_checks=3, seed=0, backend="fast",
                                 golden_every=2)
    for i in range(3):
        assert spot.wants(i)
        spot.check(i, 2)
    s = spot.summary()
    assert s["backend"] == "fast" and s["all_bit_exact"]
    assert s["n_golden_cross"] == 2         # checks 0 and 2


def test_fast_spot_check_catches_divergence():
    from repro.cfu.serve.check import (DifferentialSpotCheck,
                                       SpotCheckError)
    specs, params, x_q = _chain_fixture()
    prog = compile_network(specs, HW, HW, "fused")

    def poisoned(rng, n):
        frames = x_q[:n]
        ref = run_program(prog, frames, params).copy()
        ref.flat[0] += 1
        return frames, ref

    spot = DifferentialSpotCheck(prog, params, poisoned, every=1,
                                 max_checks=1, seed=0, backend="fast")
    with pytest.raises(SpotCheckError):
        spot.check(0, 2)
