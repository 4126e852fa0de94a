"""Compile the main path for a described TPU v5e, with no chip attached.

Interpret-mode tests never ask the TPU kernel compiler (Mosaic), so they
cannot show that a kernel is legal on the chip. These tests compile for a
``v5e:2x2`` topology that libtpu describes without hardware:

* ``fused_dsc_pallas`` at every VWW block shape (the seven PAPER_BLOCKS at
  their feature-map sizes, stride 2 and the 10x10 / 5x5 maps included);
* the whole 80x80 VWW fast-path chain at batch 8 with Pallas stage bodies,
  i.e. ``vmap`` over the ``pallas_call``.

Each compiled program must contain a ``tpu_custom_call`` (a compiled
kernel, not an interpreted one). One more test pins where the persistent
compilation cache lives. The topology is described inside a
fixture, never at import time: only one process may load libtpu, and every
test worker imports this file.
"""

import os

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
    yield
    kops.dsc_block.clear_cache()


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
