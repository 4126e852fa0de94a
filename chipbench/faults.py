"""Broken versions of the timed path, to show the check rejects them.

None of these runs in a benchmark run. ``chipbench/readings.py`` reads the
numbers they give on the chip, at the cell's own size, and the tests in
``tests/chipbench`` drive a whole run with each of them at a small size:

* ``control``: the plain reference at 4 bits in the program's place, the
  precision below the configuration's int8;
* ``stage_fault(i)``: the program's stage ``i`` (0 the stem, then the DSC
  blocks, the head, GAP+FC) hands on its output with its first row (for
  GAP+FC its first logit) one step off;
* ``half_batch``: the second half of each batch's answers is the first
  half's, as if that half had been left out;
* ``altered_answer``: one logit of each call's first image one step off.

Each is a context manager that swaps ``chipbench.system.build`` or the
program's stage builder for the time of the ``with`` block.
"""

from __future__ import annotations

import contextlib

import numpy as np

from chipbench import system

CONTROL_BITS = 4


def _off_by_one(a):
    """Every element one step away, inside the int8 range."""
    import jax.numpy as jnp
    return jnp.where(a < 127, a + 1, a - 1).astype(a.dtype)


@contextlib.contextmanager
def _swap(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def control():
    """The reference computed at ``CONTROL_BITS`` in the program's place."""
    def build(cfg, weights, ref):
        def entry(x):
            x = np.asarray(x)
            if x.ndim == 3:
                return ref.logits(cfg, weights, x[None], CONTROL_BITS)[0]
            return ref.logits(cfg, weights, x, CONTROL_BITS)
        return entry, None
    return _swap(system, "build", build)


def stage_fault(index: int):
    """The program's stage bound to params ``index`` is one step off on
    the first row of its output."""
    from repro.cfu import fastpath
    orig = fastpath._build_stage_fn

    def build_stage(stage, p, use_pallas):
        fn = orig(stage, p, use_pallas)
        if stage.block != index:
            return fn

        def broken(x, w):
            y = fn(x, w)
            return y.at[0].set(_off_by_one(y[0]))
        return broken
    return _swap(fastpath, "_build_stage_fn", build_stage)


def _wrap_entry(change):
    orig = system.build

    def build(cfg, weights, ref):
        entry, ex = orig(cfg, weights, ref)
        return (lambda x: change(np.array(entry(x)))), ex
    return _swap(system, "build", build)


def half_batch():
    """The second half of every batch answered with the first half's."""
    def change(y):
        h = len(y) // 2
        y[len(y) - h:] = y[:h]
        return y
    return _wrap_entry(change)


def altered_answer():
    """One logit of every call one step off where it is produced."""
    def change(y):
        # ``flat`` writes through whatever the layout the device handed
        # back (a TPU result may come to the host in column-major order)
        y.flat[0] = y.flat[0] + 1 if y.flat[0] < 127 else y.flat[0] - 1
        return y
    return _wrap_entry(change)
