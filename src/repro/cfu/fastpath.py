"""Jitted fast-path executor: one trace per program fingerprint.

The golden executor (``executor.run_words``) interprets encoded words one
instruction at a time — the right tool for bit-exactness, three orders of
magnitude too slow for 10k-image accuracy runs or million-request serving
simulations. This module closes that gap WITHOUT forking the semantics:
a compiled ``Program`` (or ``MultiStreamProgram``) is *lifted* once from
its encoded words into a chain of coarse stage computations, traced into
a single jitted XLA function with a ``jax.vmap`` batch axis, and cached
under a deterministic program fingerprint. The numpy interpreter stays
the golden reference; every fast-path entry point is differentially
pinned bit-exact against ``run_words`` (``tests/test_cfu_fastpath.py``
runs the schedule x streams x batch matrix).

Why lifting is sound
--------------------
A schedule changes *traffic and cycles*, never values: fused, rowtile and
layer-by-layer lowerings of a DSC block compute the same function (the
repo's oldest invariant, ``tests/test_dsc.py``). So the fast path only
has to recognise which network-level stage a CFG unit implements — the
instruction kinds are unambiguous:

* ``CONV_MAC``                      -> 3x3 stem conv
* ``DW_MAC``                        -> DSC block; without an EXP weight
                                       load, a block without expansion
                                       (t=1)
* ``WINO_MAC``                      -> DSC block (fused-winograd)
* ``GAP_RST``                       -> GAP + FC classifier unit
* ``EXP_MAC``-only                  -> head 1x1 conv

and then reuse arithmetic that is ALREADY proven bit-exact against the
interpreter. A DSC stage gets one of two bodies, chosen from the lifted
program and the platform alone:

* the Pallas kernel (``kernels.ops.dsc_block_q``, the paper's zero-buffer
  dataflow on the TPU memory hierarchy) for a kernel unit, fused (no
  ``ST_VEC``) or rowtile (a ``CFG_STRIP``), where Pallas compiles
  natively: ``use_pallas``, true by default on a TPU;
* ``core.dsc.dsc_block_reference`` everywhere else: the layer schedules
  and fused-winograd on every backend, and every DSC stage where Pallas
  would only interpret (there the kernel body runs per grid step and
  ``vmap`` serializes the batch).

Stem, head and GAP / FC run the int8 ops of
``models.mobilenetv2.forward_int8``, with int8 matmuls in float32 where
that is provably exact (``_f32_gemm_exact``). Integer accumulation plus
the shared float32 requantization sequence make every reused op
bit-identical by construction. ``use_pallas`` is part of the cache key.

Cache key semantics
-------------------
``program_fingerprint`` hashes the encoded words of every stream plus the
canonical memory-layout description — any change to the PE config, the
schedule, a tile size, the partition, or an address moves a CFG/LD/DBUF
word and therefore the fingerprint. Quantization *constants* (zero
points, ReLU6 caps, residual scales) are baked into the trace as Python
scalars, so the full cache key is ``(fingerprint, params static key)``:
two weight sets with the same quantization domains share one trace
(weights are traced arguments), while a different calibration re-traces
instead of silently reusing stale constants. Under ``jax.jit`` each new
batch *shape* compiles once more from the same trace; the Python-level
lift + stage composition is never repeated. The weights, being traced
arguments, stay on the device between calls: an executor keeps the last
set it was called with bound to device arrays and re-binds only what
changed (``FastPathExecutor``), so a weight is uploaded once, not per call.

Multi-stream programs lift to the sequential composition of their
segments: the frame pipeline changes *when* a core computes, never what;
the ragged-tail padding of ``MultiStreamRunner`` is a per-frame no-op, so
composition is exact for every batch size.
"""

from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.cfu import isa
from repro.cfu.executor import bind_input, read_output

__all__ = [
    "FastPathError", "FastPathExecutor", "program_fingerprint",
    "run_fast", "fast_executor", "cache_info", "clear_cache",
    "set_cache_limit",
]


class FastPathError(ValueError):
    """The instruction stream does not lift to a known stage chain."""


# --------------------------------------------------------------------------
# Fingerprint: encoded words + memory layout, nothing host-side
# --------------------------------------------------------------------------


def _layout_desc(layout) -> str:
    rows = [f"{r.name}|{r.space}|{r.base}|{r.size}"
            for r in sorted(layout.regions.values(), key=lambda r: r.name)]
    rows += [f"dbuf:{name}|{r.space}|{r.base}|{r.size}"
             for name, r in sorted(layout.dbuf.items())]
    rows.append(f"dram={layout.dram_size};sram={layout.sram_size}")
    return ";".join(rows)


def _streams_of(prog) -> List:
    return list(getattr(prog, "streams", None) or [prog])


def program_fingerprint(prog) -> str:
    """Deterministic identity of a compiled program: sha256 over the
    encoded words of every stream plus the canonical layout description.

    Anything that changes execution — schedule, PE config, tile sizes,
    partition, addresses — changes a word or a region and therefore the
    fingerprint; host-side niceties (names in ``meta``) do not.
    """
    h = hashlib.sha256()
    for p in _streams_of(prog):
        h.update(isa.encode_program(p).tobytes())
        h.update(b"|")
    h.update(_layout_desc(prog.meta["layout"]).encode())
    return h.hexdigest()


# --------------------------------------------------------------------------
# Lifting: decoded words -> stage descriptors
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Stage:
    """One lifted network-level stage (the unit between CFG words)."""

    kind: str            # "stem" | "dsc" | "dw" (t=1) | "head" | "gapfc"
    block: int           # LD_WGT.block -> params index
    cin: int
    cmid: int
    cout: int
    stride: int
    h: int
    w: int
    kernel: bool = False  # dsc/dw: a kernel unit (fused or rowtile)
    tile_rows: int = 4    # kernel granularity (from CFG_STRIP if set)
    gap_n: int = 0        # gapfc divisor (GAP_FIN operand)

    def out_shape(self, in_shape: Tuple[int, ...]) -> Tuple[int, ...]:
        h2, w2 = -(-self.h // self.stride), -(-self.w // self.stride)
        if self.kind == "gapfc":
            return (self.cout,)
        return (h2, w2, self.cout)


def _lift_stream(instrs: Sequence[isa.Instr]) -> List[_Stage]:
    """Split one decoded stream at CFG boundaries and classify each unit."""
    units: List[List[isa.Instr]] = []
    for ins in instrs:
        if ins.op == "CFG":
            units.append([ins])
        elif units:
            units[-1].append(ins)
        elif ins.op not in ("CFG_PE", "CFG_CORE", "HALT"):
            raise FastPathError(f"instruction {ins.op} before first CFG")
    stages = []
    for unit in units:
        cin, cmid, cout, stride, h, w = unit[0].args
        for ins in unit:
            if ins.op == "CFG_X":
                cin, cmid, cout = isa.widen_cfg(cin, cmid, cout, ins.args)
        ops = {i.op for i in unit}
        wgt = {i.args[0]: i.args[1] for i in unit if i.op == "LD_WGT"}
        if "CONV_MAC" in ops:
            stages.append(_Stage("stem", wgt[isa.WGT_CONV], cin, cmid,
                                 cout, stride, h, w))
        elif "GAP_RST" in ops:
            n = next(i.args[0] for i in unit if i.op == "GAP_FIN")
            stages.append(_Stage("gapfc", wgt[isa.WGT_PROJ], cin, cmid,
                                 cout, stride, h, w, gap_n=n))
        elif "DW_MAC" in ops:
            strip = next((i.args[0] for i in unit if i.op == "CFG_STRIP"),
                         0)
            if strip:                    # rowtile: invert (t-1)*s + k
                kernel, tr = True, max(1, (strip - isa.KERNEL) // stride
                                       + 1)
            else:                        # fused pixel-wise stores nothing;
                kernel, tr = "ST_VEC" not in ops, 4   # layer-* store F1/F2
            if isa.WGT_EXP in wgt:
                kind, block = "dsc", wgt[isa.WGT_EXP]
            else:                        # t=1: no expansion weights
                kind, block = "dw", wgt[isa.WGT_DW]
            stages.append(_Stage(kind, block, cin, cmid, cout, stride, h,
                                 w, kernel=kernel, tile_rows=tr))
        elif "WINO_MAC" in ops:
            # fused-winograd: no DW_MAC/LD_WIN in the stream, so this must
            # be checked before the EXP_MAC-only head classification
            stages.append(_Stage("dsc", wgt[isa.WGT_EXP], cin, cmid, cout,
                                 stride, h, w))
        elif "EXP_MAC" in ops:
            stages.append(_Stage("head", wgt[isa.WGT_EXP], cin, cmid,
                                 cout, stride, h, w))
        else:
            raise FastPathError(
                f"CFG unit with ops {sorted(ops)} matches no known stage")
    return stages


def _lift_program(prog) -> List[_Stage]:
    stages: List[_Stage] = []
    for p in _streams_of(prog):
        stages.extend(
            _lift_stream(isa.decode_words(isa.encode_program(p))))
    return stages


# --------------------------------------------------------------------------
# Stage descriptors -> jitted computation (weights stay traced arguments)
# --------------------------------------------------------------------------

_STAGE_ARRAYS = {
    "stem": ("w_conv", "b_conv", "m_exp"),
    "dsc": ("w_exp", "w_dw", "w_proj", "b_exp", "b_dw", "b_proj",
            "m_exp", "m_dw", "m_proj"),
    "dw": ("w_dw", "w_proj", "b_dw", "b_proj", "m_dw", "m_proj"),
    "head": ("w_exp", "b_exp", "m_exp"),
    "gapfc": ("w_proj", "b_proj", "m_proj"),
}


def _scale_bits(qp) -> str:
    return float(np.asarray(qp.scale)).hex()


def _static_key_of(stage: _Stage, p) -> Tuple:
    """The quantization constants a stage bakes into its trace (part of
    the cache key: same fingerprint + same constants => same trace)."""
    if stage.kind == "stem":
        return ("stem", p.qp_in.zero_point, p.qp_f1.zero_point, p.q6_f1)
    if stage.kind == "head":
        return ("head", p.qp_f1.zero_point, p.q6_f1)
    if stage.kind == "gapfc":
        return ("gapfc", p.qp_out.zero_point)
    spec = p.spec
    return (stage.kind, spec.cin, spec.cmid, spec.cout, spec.stride,
            p.qp_in.zero_point, p.qp_f1.zero_point, p.qp_f2.zero_point,
            p.qp_out.zero_point, p.q6_f1, p.q6_f2,
            _scale_bits(p.qp_in), _scale_bits(p.qp_out))


def _check_stage_params(stage: _Stage, p):
    """Fail fast (and clearly) when params don't match the lifted stream."""
    need = {"stem": "w_conv", "dsc": "w_dw", "dw": "w_dw", "head": "w_exp",
            "gapfc": "w_proj"}[stage.kind]
    if getattr(p, need, None) is None:
        raise FastPathError(
            f"params[{stage.block}] ({type(p).__name__}) lacks {need!r} "
            f"for a lifted {stage.kind} stage")
    if stage.kind in ("dsc", "dw") and (p.spec.cin, p.spec.cmid, p.spec.cout,
                                p.spec.stride) != (stage.cin, stage.cmid,
                                                   stage.cout,
                                                   stage.stride):
        raise FastPathError(
            f"params[{stage.block}] spec {p.spec} mismatches lifted DSC "
            f"geometry ({stage.cin},{stage.cmid},{stage.cout},"
            f"s{stage.stride})")


# float32 holds every integer of magnitude < 2^24 exactly, and a K-term
# int8 dot is bounded by K * 128^2 — so f32 GEMM is bit-exact iff:
_F32_EXACT_LIMIT = 1 << 24


def _f32_gemm_exact(k: int) -> bool:
    """True when a K-term int8 x int8 contraction is exact in float32."""
    return k * 128 * 128 < _F32_EXACT_LIMIT


def _build_stage_fn(stage: _Stage, p, use_pallas: bool):
    """Close over STATIC quantization constants only; weight tensors are
    traced arguments (dict ``w``), so one trace serves any weight values
    in the same quantization domains."""
    import jax
    import jax.numpy as jnp

    from repro.core import dsc as dsc_mod
    from repro.core import quant

    def mm(a2d, w2d, k):
        """int8 (N,K) @ int8 (K,M) -> int32, via f32 SGEMM when exact."""
        if _f32_gemm_exact(k):
            return (a2d.astype(jnp.float32) @ w2d.astype(jnp.float32)
                    ).astype(jnp.int32)
        return a2d.astype(jnp.int32) @ w2d.astype(jnp.int32)

    if stage.kind == "stem":
        zp_in, zp_f1 = p.qp_in.zero_point, p.qp_f1.zero_point
        q6, s = p.q6_f1, stage.stride
        cin, cout = stage.cin, stage.cout
        conv_dt = (jnp.float32 if _f32_gemm_exact(9 * stage.cin)
                   else jnp.int32)

        def stem_fn(x, w):
            # im2col: 9 strided taps concatenated on the channel axis, then
            # one GEMM that keeps (H2, W2) as free dimensions. A reshape to
            # (H2*W2, 9*Cin) and back makes XLA:TPU put the pixels of the
            # int32 accumulator in the lanes and relay it out channel-minor
            # before a separate requantization; kept 3-D, the bias, requant
            # and int8 convert fuse into the GEMM's output, so the int8 map
            # is all the stem writes
            xp = jnp.pad(x, ((1, 1), (1, 1), (0, 0)),
                         constant_values=zp_in)
            h2, w2 = -(-x.shape[0] // s), -(-x.shape[1] // s)
            cols = [jax.lax.slice(
                xp, (dy, dx, 0),
                (dy + (h2 - 1) * s + 1, dx + (w2 - 1) * s + 1, cin),
                (s, s, 1)) for dy in range(3) for dx in range(3)]
            patches = jnp.concatenate(cols, axis=-1).astype(conv_dt)
            wf = w["w_conv"].reshape(9 * cin, cout).astype(conv_dt)
            acc = jnp.einsum("hwk,kc->hwc", patches, wf).astype(jnp.int32)
            return quant.requantize(acc + w["b_conv"], w["m_exp"], zp_f1,
                                    relu=True, relu6_max_q=q6)
        return stem_fn

    if stage.kind == "head":
        zp_f1, q6 = p.qp_f1.zero_point, p.q6_f1
        cin, cmid = stage.cin, stage.cmid

        def head_fn(x, w):
            h, wd = x.shape[0], x.shape[1]
            acc = mm(x.reshape(h * wd, cin), w["w_exp"],
                     cin).reshape(h, wd, cmid) + w["b_exp"]
            return quant.requantize(acc, w["m_exp"], zp_f1, relu=True,
                                    relu6_max_q=q6)
        return head_fn

    if stage.kind == "gapfc":
        zp_out, n, cin = p.qp_out.zero_point, stage.gap_n, stage.cin

        def gapfc_fn(x, w):
            g = x.astype(jnp.int32).sum(axis=(0, 1))
            g = jnp.round(g.astype(jnp.float32) / jnp.float32(n))
            g = jnp.clip(g.astype(jnp.int32), -128, 127).astype(jnp.int8)
            acc = mm(g[None], w["w_proj"], cin)[0] + w["b_proj"]
            return quant.requantize(acc, w["m_proj"], zp_out)
        return gapfc_fn

    # --- DSC block: the kernel on a kernel unit where Pallas compiles
    # natively, else the layer-by-layer reference; either way the block's
    # params with the weight tensors swapped for the traced arguments
    if use_pallas and stage.kernel:
        from repro.kernels import ops as kops
        tile_rows = stage.tile_rows

        def dsc_kernel_fn(x, w):
            return kops.dsc_block_q(x, dataclasses.replace(p, **w),
                                    tile_rows=tile_rows)
        return dsc_kernel_fn

    def dsc_ref_fn(x, w):
        return dsc_mod.dsc_block_reference(x, dataclasses.replace(p, **w))
    return dsc_ref_fn


_DTYPES = {"w": np.int8, "b": np.int32, "m": np.float32}   # by name prefix

# A large call is uploaded and run in chunks, so that the chain starts on
# the first while the others are in transit. Two limits keep a chunk
# worth it. It moves at least _CHUNK_MIN_BYTES: on a TPU v5e the fast
# path's readback times fit 1.1 ms + bytes / 5.3 GB/s (4.9 MB a call at
# 80x80, 38.5 MB at 224x224, batch 256), so 8 MiB is about 1.6 ms of
# transfer, more than the host takes to dispatch one chunk's chain. And
# it holds a multiple of _CHUNK_IMAGES images: XLA:TPU lays an int8
# batch of 224x224x3 images out with the batch in the 128 lanes at 128,
# 256, 384 or 512 images, but with the width there at 32, 64 or 192,
# which turns the stem's nine stride-2 taps into lane-strided slices of
# about 3 ms a call each on a v5e (64-image chunks of a 256-image call).
_CHUNK_MIN_BYTES = 8 << 20
_CHUNK_IMAGES = 128


def _chunk_count(n: int, image_bytes: int) -> int:
    """The equal chunks a batch of ``n`` images of ``image_bytes`` each is
    uploaded and run in: the largest divisor of ``n`` whose chunks each
    hold a multiple of ``_CHUNK_IMAGES`` images and move at least
    ``_CHUNK_MIN_BYTES``, else 1."""
    for k in range(min(n // _CHUNK_IMAGES,
                       n * image_bytes // _CHUNK_MIN_BYTES), 1, -1):
        if n % (k * _CHUNK_IMAGES) == 0:
            return k
    return 1


# --------------------------------------------------------------------------
# The executor object + fingerprint cache
# --------------------------------------------------------------------------


class FastPathExecutor:
    """One lifted + traced program; ``__call__`` matches ``run_program`` /
    ``run_multistream`` (minus stats/tracer — the interpreter owns those).

    The stage arrays stay on the device between calls. Each call binds the
    params it is given to device arrays, per stage array and by what the
    caller passed:

    * a ``jax.Array`` of the stage's dtype on the executor's ``device``
      is handed to the chain as it is: no copy back, no upload;
    * any other ``jax.Array`` is moved and cast on the device once, and
      the result reused while the caller passes the same object (the
      executor holds it, so its identity stays unique; it is immutable);
    * a host array is uploaded once and reused while its contents equal a
      host snapshot taken at the upload (``np.array_equal`` each call), so
      a weight changed in place is uploaded again.

    Only the last bound set is kept: calling with another weight set of
    the same quantization constants (the executor ``fast_executor``
    shares between them) re-binds, and the old device copies are freed.
    The stage arrays are never donated; only the input is.
    ``weight_uploads`` counts the arrays uploaded or converted over the
    executor's life, ``weight_binds`` the calls that had any.

    A large batch is uploaded and run in K equal chunks (``_chunk_count``
    of the batch and the bytes an image: the most chunks that each hold
    a multiple of 128 images and move at least 8 MiB). The chain of one
    chunk waits only for that chunk's input, so it runs while the next
    is in transit; each image's logits depend on that image alone, so
    the result is the same. A 256-image batch of 224x224x3 (38.5 MB)
    goes in 2 chunks; a VWW batch (4.9 MB), small batches and single
    frames go whole (K = 1), as one upload and one dispatch.

    Each call runs four phases, each inside a ``jax.profiler``
    annotation on the profiler's clock (the clock of the device's
    ``XLA Ops``), in this order:

    * ``fastpath.weights``: the stage arrays bound as above, without
      uploading (args ``d2h_arrays``, always 0, ``reused`` and
      ``uploaded``, the arrays this call uploads or converts);
    * ``fastpath.put_input``: one chunk of the input uploaded (``bytes``
      of the chunk, ``chunks`` = K);
    * ``fastpath.launch``: the uploads the binding asked for, if any
      (``arrays``, ``bytes``; in the first chunk only, 0 once bound),
      then the jitted chain dispatched on that chunk;
    * ``fastpath.readback``: the logits of every chunk copied back
      together, which waits for the device, and joined.

    ``put_input`` and ``launch`` alternate once per chunk, so no chain
    waits behind the upload of a later chunk, and a call with K = 1 holds
    each phase once. ``chunked_calls`` counts the calls with
    K > 1, and ``n_traces`` the chunk shapes compiled.

    The input goes up through a donated identity ``jit``, which on a TPU
    v5e costs about 0.2 ms a call less than ``jax.device_put``. Each
    dispatch that compiles, those of the first chunk of the first call
    with a new chunk shape, runs inside a nested ``fastpath.compile``.
    With no profiler running an annotation costs about a microsecond.
    """

    def __init__(self, prog, params: Sequence,
                 use_pallas: Optional[bool] = None):
        import jax

        self.meta = prog.meta
        self.use_pallas = _resolve_use_pallas(use_pallas)
        self.fingerprint = program_fingerprint(prog)
        self.stages = _lift_program(prog)
        if not self.stages:
            raise FastPathError("program lifts to zero stages")
        for st in self.stages:
            _check_stage_params(st, params[st.block])
        self.static_key = tuple(_static_key_of(st, params[st.block])
                                for st in self.stages)
        # shape continuity: lift-time validation, not run-time surprise
        shape = tuple(self.meta["in_shape"])
        for st in self.stages:
            if st.kind != "gapfc" and shape != (st.h, st.w, st.cin):
                raise FastPathError(
                    f"stage {st.kind}@block{st.block} wants input "
                    f"({st.h},{st.w},{st.cin}), chain carries {shape}")
            shape = st.out_shape(shape)
        out_shape = tuple(self.meta["out_shape"])
        if int(np.prod(shape)) != int(np.prod(out_shape)):
            raise FastPathError(
                f"lifted chain ends at {shape}, program output region "
                f"holds {out_shape}")
        fns = [_build_stage_fn(st, params[st.block], self.use_pallas)
               for st in self.stages]

        def chain(x, wlist):
            for fn, w in zip(fns, wlist):
                x = fn(x, w)
            return x

        # public so callers can ``.lower(...)`` it to inspect the program
        self.jitted = jax.jit(jax.vmap(chain, in_axes=(0, None)))
        self._put = jax.jit(lambda x: x, donate_argnums=0)
        self._shapes: set = set()       # input shapes launched so far
        self.device = jax.devices()[0]
        # the stage arrays in chain order: (params index, field, dtype)
        self._slots = [(st.block, name, np.dtype(_DTYPES[name[0]]))
                       for st in self.stages
                       for name in _STAGE_ARRAYS[st.kind]]
        # per slot, the last bound (source, host snapshot, device array)
        self._bound: List[Optional[Tuple]] = [None] * len(self._slots)
        self.weight_uploads = 0
        self.weight_binds = 0
        self.chunked_calls = 0

    @property
    def n_traces(self) -> int:
        """The distinct input chunk shapes this executor has compiled."""
        return len(self._shapes)

    @staticmethod
    def _dispatch(fn, new: bool, *args):
        """``fn(*args)``; inside a ``fastpath.compile`` span when ``new``
        (the first chunk with a new shape compiles ``fn`` for it)."""
        if not new:
            return fn(*args)
        import jax
        with jax.profiler.TraceAnnotation("fastpath.compile"):
            return fn(*args)

    def _plan(self, params: Sequence) -> Tuple[list, List[int]]:
        """The stage arrays flat in chain order, each its device copy where
        one is bound, and the slots that still need an upload (left as
        the caller's array)."""
        import jax
        flat, todo = [], []
        for k, (block, name, dt) in enumerate(self._slots):
            v = getattr(params[block], name)
            held = self._bound[k]
            if held is not None and held[0] is v:     # bound before
                v = held[2]
            elif isinstance(v, jax.Array):
                if v.dtype == dt and v.devices() == {self.device}:
                    self._bound[k] = (v, None, v)
                else:
                    todo.append(k)
            else:
                v = np.asarray(v)
                if (held is not None and held[1] is not None
                        and np.array_equal(held[1], v)):
                    v = held[2]
                else:
                    todo.append(k)
            flat.append(v)
        return flat, todo

    def _upload(self, flat: list, todo: List[int]) -> int:
        """Binds the slots in ``todo`` to device copies, in place in
        ``flat``; returns the bytes put on the device."""
        if not todo:
            return 0
        import jax
        n_bytes = 0
        for k in todo:
            v, dt = flat[k], self._slots[k][2]
            if isinstance(v, jax.Array):
                dev = jax.device_put(v, self.device).astype(dt)
                self._bound[k] = (v, None, dev)
            else:
                # the upload reads the executor's own copy, never the
                # caller's buffer, which may change in place
                snap = np.array(v)
                dev = jax.device_put(snap.astype(dt, copy=False),
                                     self.device)
                self._bound[k] = (None, snap, dev)
            flat[k] = dev
            n_bytes += dev.nbytes
        self.weight_uploads += len(todo)
        self.weight_binds += 1
        return n_bytes

    def _nest(self, flat: list) -> List[Dict[str, object]]:
        it = iter(flat)
        return [{name: next(it) for name in _STAGE_ARRAYS[st.kind]}
                for st in self.stages]

    def weights_of(self, params: Sequence) -> List[Dict[str, object]]:
        """The stage arrays as the chain takes them (per stage, by field
        name), bound on the device the way ``__call__`` binds them."""
        flat, todo = self._plan(params)
        self._upload(flat, todo)
        return self._nest(flat)

    def __call__(self, x_q, params: Sequence) -> np.ndarray:
        import jax
        span = jax.profiler.TraceAnnotation
        x_q, batched = bind_input(x_q, self.meta)
        parts = np.split(x_q, _chunk_count(len(x_q), x_q[0].nbytes))
        new = parts[0].shape not in self._shapes
        with span("fastpath.weights") as s:
            flat, todo = self._plan(params)
            s.set_metadata(d2h_arrays=0, reused=len(flat) - len(todo),
                           uploaded=len(todo))
        ys = []
        # chunk i + 1 goes up after chain i is queued, never before it:
        # 0.6 ms a call faster than all uploads first (v5e, 2 chunks)
        for i, part in enumerate(parts):
            with span("fastpath.put_input") as s:
                s.set_metadata(bytes=part.nbytes, chunks=len(parts))
                x_dev = self._dispatch(self._put, new and not i, part)
            with span("fastpath.launch") as s:
                s.set_metadata(arrays=len(todo),
                               bytes=self._upload(flat, todo))
                todo = []                 # bound: later chunks reuse
                ys.append(self._dispatch(self.jitted, new and not i, x_dev,
                                         self._nest(flat)))
        self._shapes.add(parts[0].shape)
        self.chunked_calls += len(parts) > 1
        with span("fastpath.readback"):
            out_shape = tuple(self.meta["out_shape"])
            y = np.concatenate(jax.device_get(ys)).reshape(
                (len(x_q),) + out_shape)
            return y if batched else y[0]


_CACHE: "OrderedDict[Tuple[str, Tuple, bool], FastPathExecutor]" = \
    OrderedDict()
_HITS = 0
_MISSES = 0
_EVICTIONS = 0
#: Default trace-cache capacity. Generous (a trace is small; the VWW
#: matrix tests trace a few dozen programs) but BOUNDED: long serving
#: runs cycling through many compiled design points no longer grow the
#: cache without limit. ``set_cache_limit`` reconfigures it.
_DEFAULT_CACHE_LIMIT = 128
_LIMIT = _DEFAULT_CACHE_LIMIT


def set_cache_limit(n: int) -> None:
    """Bound the trace cache to ``n`` executors (LRU eviction).

    Shrinking below the current size evicts the least-recently-used
    entries immediately. Eviction only drops the cached trace — a later
    request for the same program re-lifts and re-traces, bit-exact
    (pinned by the eviction test in ``tests/test_cfu_fastpath.py``).
    """
    global _LIMIT
    if n < 1:
        raise ValueError(f"cache limit must be >= 1, got {n}")
    _LIMIT = n
    _evict_to_limit()


def _evict_to_limit() -> None:
    global _EVICTIONS
    while len(_CACHE) > _LIMIT:
        _CACHE.popitem(last=False)
        _EVICTIONS += 1


def _resolve_use_pallas(flag: Optional[bool]) -> bool:
    """Default: kernel stage bodies only where Pallas compiles natively
    (TPU); elsewhere every DSC stage runs the reference body."""
    if flag is not None:
        return bool(flag)
    from repro.kernels import ops as kops
    return not kops.default_interpret()


def fast_executor(prog, params: Sequence,
                  use_pallas: Optional[bool] = None) -> FastPathExecutor:
    """Cache lookup: (program fingerprint, params static key, stage-body
    backend) -> executor.

    A hit returns the SAME object (same trace); a changed PE config,
    schedule, layout, quantization domain, or forced ``use_pallas`` misses
    and traces fresh — never stale reuse.
    """
    global _HITS, _MISSES
    fp = program_fingerprint(prog)
    up = _resolve_use_pallas(use_pallas)
    # cheap pre-check: an executor under this fingerprint knows its lifted
    # stages, so reuse them to key the params constants without re-lifting
    for (cfp, _, cup), ex in _CACHE.items():
        if cfp == fp and cup == up:
            key = (fp, tuple(_static_key_of(st, params[st.block])
                             for st in ex.stages), up)
            hit = _CACHE.get(key)
            if hit is not None:
                _HITS += 1
                _CACHE.move_to_end(key)     # LRU: refresh recency
                return hit
            break
    ex = FastPathExecutor(prog, params, use_pallas=up)
    _CACHE[(fp, ex.static_key, up)] = ex
    _MISSES += 1
    _evict_to_limit()
    return ex


def run_fast(prog, x_q, params: Sequence,
             use_pallas: Optional[bool] = None) -> np.ndarray:
    """Drop-in fast-path twin of ``run_program`` / ``run_multistream``:
    same input conventions (single frame or batch), same output, computed
    by the cached jitted trace instead of the word interpreter."""
    return fast_executor(prog, params, use_pallas=use_pallas)(x_q, params)


def cache_info() -> Dict[str, object]:
    return {"size": len(_CACHE), "hits": _HITS, "misses": _MISSES,
            "evictions": _EVICTIONS, "limit": _LIMIT,
            "fingerprints": sorted({fp for fp, *_ in _CACHE})}


def clear_cache() -> None:
    global _HITS, _MISSES, _EVICTIONS, _LIMIT
    _CACHE.clear()
    _HITS = 0
    _MISSES = 0
    _EVICTIONS = 0
    _LIMIT = _DEFAULT_CACHE_LIMIT
