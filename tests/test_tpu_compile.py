"""Compile the main path for a described TPU v5e, with no chip attached.

Interpret-mode tests never ask the TPU kernel compiler (Mosaic), so they
cannot show that a kernel is legal on the chip. These tests compile for a
``v5e:2x2`` topology that libtpu describes without hardware:

* ``fused_dsc_pallas`` at every VWW block shape (the seven PAPER_BLOCKS at
  their feature-map sizes, stride 2 and the 10x10 / 5x5 maps included);
* the whole 80x80 VWW fast-path chain at batch 8 with Pallas stage bodies,
  i.e. ``vmap`` over the ``pallas_call``;
* the kernel of a block without expansion (``dw_pallas``), at
  MobileNetV2's first block (112x112x32) and at a stride-2 odd map;
* the whole 224x224 MobileNetV2 1.0 chain of the benchmark's
  ``mnv2-224-fused`` configuration at batch 8: 16 expanding kernels, one
  without expansion, 112x112 maps, a 1280-channel head.

Each compiled program must contain a ``tpu_custom_call`` (a compiled
kernel, not an interpreted one). One more test pins where the persistent
compilation cache lives. The topology is described inside a
fixture, never at import time: only one process may load libtpu, and every
test worker imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops
from repro.launch import compile_cache
from repro.kernels.fused_dsc import fused_dsc_pallas
from repro.models import mobilenetv2 as mnv2


VWW_BLOCKS = [(name, spec, hw) for (name, spec), hw
              in zip(mnv2.block_specs(), mnv2.block_input_hw(80))]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe the chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """``kops.default_interpret`` answers by the CPU backend; steer it to
    the chip's answer, and drop ``dsc_block``'s traces on both sides so no
    interpreted trace is reused here and no compiled one leaks out."""
    monkeypatch.setattr(kops, "default_interpret", lambda: False)
    kops.dsc_block.clear_cache()
    kops.dw_block.clear_cache()
    yield
    kops.dsc_block.clear_cache()
    kops.dw_block.clear_cache()


@pytest.mark.parametrize("name,spec,hw", VWW_BLOCKS,
                         ids=[b[0] for b in VWW_BLOCKS])
def test_fused_dsc_compiles_for_v5e(one_chip, name, spec, hw):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cin, cmid, cout = spec.cin, spec.cmid, spec.cout

    args = [sds((hw, hw, cin), jnp.int8), sds((cin, cmid), jnp.int8),
            sds((9, cmid), jnp.int8), sds((cmid, cout), jnp.int8),
            sds((cmid,), jnp.int32), sds((cmid,), jnp.int32),
            sds((cout,), jnp.int32), sds((cmid,), jnp.float32),
            sds((cmid,), jnp.float32), sds((cout,), jnp.float32)]

    def block(*a):
        return fused_dsc_pallas(*a, stride=spec.stride,
                                zps=(3, -128, -128, 5),
                                q6=(100, 90), tile_rows=4, interpret=False)

    text = jax.jit(block).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cin,cout,stride,hw", [(32, 16, 1, 112),
                                               (8, 16, 2, 11)])
def test_dw_block_compiles_for_v5e(one_chip, cin, cout, stride, hw):
    from repro.kernels.fused_dsc import dw_pallas

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [sds((hw, hw, cin), jnp.int8), sds((9, cin), jnp.int8),
            sds((cin, cout), jnp.int8), sds((cin,), jnp.int32),
            sds((cout,), jnp.int32), sds((cin,), jnp.float32),
            sds((cout,), jnp.float32)]

    def block(*a):
        return dw_pallas(*a, stride=stride, zps=(-128, -128, 5), q6=90,
                         tile_rows=4, interpret=False)

    text = jax.jit(block).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _zero_params(cfg):
    """Stage records of the configuration's shapes, all zero: a compile
    needs shapes and quantization constants, not values."""
    from repro.cfu.network import CFUFCParams, CFUHeadParams, CFUStemParams
    from repro.core.dsc import DSCBlockSpec, QuantizedDSCParams
    from repro.core.quant import QParams

    def z(*shape, dt=np.int8):
        return np.zeros(shape, dt)
    q6, lin = QParams(0.03125, -128), QParams(0.0625, 0)
    c0, hc, nc = cfg["blocks"][0][1], cfg["head_ch"], cfg["n_classes"]
    specs = []
    params = [CFUStemParams(z(3, 3, cfg["img_ch"], c0), z(c0, dt=np.int32),
                            z(c0, dt=np.float32), QParams(1 / 128, 0), q6,
                            64)]
    for name, ci, cm, co, s in cfg["blocks"]:
        spec = DSCBlockSpec(ci, cm, co, s)
        specs.append((name, spec))
        e = spec.has_expansion
        params.append(QuantizedDSCParams(
            spec, z(ci, cm) if e else None, z(3, 3, cm), z(cm, co),
            z(cm, dt=np.int32) if e else None, z(cm, dt=np.int32),
            z(co, dt=np.int32), q6 if len(params) == 1 else lin, q6, q6,
            lin, z(cm, dt=np.float32) if e else None, z(cm, dt=np.float32),
            z(co, dt=np.float32), 64, 64))
    c_last = cfg["blocks"][-1][3]
    params += [CFUHeadParams(z(c_last, hc), z(hc, dt=np.int32),
                             z(hc, dt=np.float32), lin, q6, 64),
               CFUFCParams(z(hc, nc), z(nc, dt=np.int32),
                           z(nc, dt=np.float32), lin)]
    return specs, params


def _mnv2_224_compiled(one_chip, batch):
    """The ``mnv2-224-fused`` chain compiled for one described v5e chip at
    ``batch`` images, as HLO text; and the configuration."""
    import json
    import pathlib
    from repro.cfu.compiler import compile_vww_network
    from repro.cfu.fastpath import FastPathExecutor
    cfg = json.loads((pathlib.Path(__file__).resolve().parents[1] /
                      "chipbench/configs/mnv2-224-fused.json").read_text())
    specs, params = _zero_params(cfg)
    prog = compile_vww_network(specs, cfg["img_hw"], cfg["schedule"],
                               img_ch=cfg["img_ch"], head_ch=cfg["head_ch"],
                               n_classes=cfg["n_classes"])
    ex = FastPathExecutor(prog, params, use_pallas=True)
    assert [st.kind for st in ex.stages].count("dw") == 1

    def sds(a):
        a = np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    x = jax.ShapeDtypeStruct((batch, 224, 224, 3), jnp.int8,
                             sharding=one_chip)
    weights = jax.tree.map(sds, ex.weights_of(params))
    return ex.jitted.lower(x, weights).compile().as_text(), cfg


def test_mnv2_224_chain_compiles_for_v5e(one_chip, compiled_kernels):
    text, cfg = _mnv2_224_compiled(one_chip, 8)
    assert text.count("tpu_custom_call") >= len(cfg["blocks"])


def _hlo_computations(text):
    """Computation name -> its instruction lines, from compiled HLO text;
    the entry computation also under ``"ENTRY"``."""
    comps, body = {}, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            body = comps.setdefault(head.group(2), [])
            if head.group(1):
                comps["ENTRY"] = body
        elif line == "}":
            body = None
        elif body is not None:
            body.append(line)
    return comps


_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][\w\-]*)\((.*)$")


def test_mnv2_224_stem_requantizes_inside_its_gemm(one_chip,
                                                   compiled_kernels):
    """At the benchmark's 128-image chunk the stem's int32 accumulator
    never reaches HBM: no entry instruction outputs a 32-bit array of the
    stem map's size (it would be the GEMM's raw output or a relayout of
    it), and the first kernel reads the int8 map straight from the fusion
    that holds the stem's GEMM."""
    batch = 128
    text, cfg = _mnv2_224_compiled(one_chip, batch)
    comps = _hlo_computations(text)
    stem_elems = batch * 112 * 112 * cfg["blocks"][0][1]
    instrs = {}
    for line in comps["ENTRY"]:
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        name, out_type, op, rest = m.groups()
        instrs[name] = (op, rest)
        for dt, dims in re.findall(r"\b([suf]32)\[([\d,]*)\]", out_type):
            n = int(np.prod([int(d) for d in dims.split(",") if d]))
            assert n != stem_elems, f"%{name} outputs {dt}[{dims}]"

    first_kernel = next(rest for op, rest in instrs.values()
                        if op == "custom-call"
                        and 'custom_call_target="tpu_custom_call"' in rest)
    operand = re.match(r"%([\w.\-]+)", first_kernel).group(1)
    op, rest = instrs[operand]
    assert op == "fusion", f"the first kernel reads %{operand}, a {op}"

    def holds_gemm(comp):
        for line in comps[comp]:
            m = _HLO_INSTR.match(line)
            if m and m.group(3) in ("dot", "convolution"):
                return True
            if any(holds_gemm(c) for c in
                   re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", line)):
                return True
        return False

    called = re.search(r"calls=%([\w.\-]+)", rest).group(1)
    assert holds_gemm(called), f"%{operand} ({called}) holds no dot"


@pytest.fixture(scope="module")
def vww80():
    from repro.cfu.network import vww_cfu_params
    net = mnv2.init_and_quantize(jax.random.PRNGKey(0), img_hw=80)
    return vww_cfu_params(net)


@pytest.mark.parametrize("sched", ["fused", "fused-rowtile"])
def test_vww80_fast_path_chain_compiles_for_v5e(one_chip, compiled_kernels,
                                                vww80, sched):
    from repro.cfu.compiler import compile_vww_network
    from repro.cfu.fastpath import FastPathExecutor
    params = vww80
    prog = compile_vww_network(mnv2.block_specs(), 80, sched)
    ex = FastPathExecutor(prog, params, use_pallas=True)

    def sds(a):
        a = np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    x = jax.ShapeDtypeStruct((8, 80, 80, 3), jnp.int8, sharding=one_chip)
    weights = jax.tree.map(sds, ex.weights_of(params))
    text = ex.jitted.lower(x, weights).compile().as_text()
    n_blocks = len(mnv2.PAPER_BLOCKS)
    assert text.count("tpu_custom_call") >= n_blocks


def test_compile_cache_dir(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and the code then sets nothing;
    otherwise the cache lives at the fixed, git-ignored ``.jax_cache``."""
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/outside")
        assert compile_cache.enable_compile_cache() == "/from/outside"
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = compile_cache.DEFAULT_DIR.parent
        assert compile_cache.DEFAULT_DIR == repo / ".jax_cache"
        assert (repo / "src" / "repro" / "launch").is_dir()
        assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
        assert compile_cache.enable_compile_cache() == str(
            compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(
            compile_cache.DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
