"""Deliberately-broken inputs for the ``repro.analysis`` checkers.

Each fixture seeds exactly one hazard class and is used from two
places: ``scripts/analyze.py --fixture <name>`` (must exit nonzero —
the CI self-test that the gate actually gates) and
``tests/test_analysis.py`` (asserts the specific finding).  Keeping
them importable from ``repro.analysis`` rather than inlined in the
test file matters for the DMA fixture: ``simulate_dma_pairing`` swaps
the kernel's module-level ``pl`` / ``pltpu`` / ``jnp`` for stubs via
``kernel.__globals__``, so the broken kernel must resolve those names
as globals of its defining module (a closure over the real modules
would dodge the patch and crash on ``pl.program_id`` outside a trace).
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# pallas: unmatched DMA wait
# ---------------------------------------------------------------------------

def make_unmatched_wait_kernel(b_tile: int, n_tiles: int, k_slab: int,
                               packed: bool, fuse_self: bool):
    """Same two-slot K-slab rotation as the real ``_make_tiled_kernel``
    but the wait is fenced to ``ki + 1 < nk``: the LAST slab's copies
    are consumed un-waited and leak past the output-tile boundary.
    ``simulate_dma_pairing`` must flag every leaked copy."""

    def kernel(idx_ref, w_ref, *refs):
        if fuse_self:
            wself_ref, self_ref, feat_ref, out_ref, rows_ref, acc_ref, \
                sems = refs
        else:
            feat_ref, out_ref, rows_ref, acc_ref, sems = refs
        ti = pl.program_id(1)
        ki = pl.program_id(2)
        nk = pl.num_programs(2)

        def slab_copies(slab, slot):
            copies = []
            for j in range(k_slab):
                for i in range(b_tile):
                    nid = idx_ref[i, slab * k_slab + j]
                    copies.append(pltpu.make_async_copy(
                        feat_ref.at[nid * n_tiles + ti],
                        rows_ref.at[slot, j, i],
                        sems.at[slot, j, i]))
            return copies

        @pl.when(ki == 0)
        def _init():
            for c in slab_copies(0, 0):
                c.start()
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(ki + 1 < nk)
        def _prefetch_next():
            for c in slab_copies(ki + 1, (ki + 1) % 2):
                c.start()

        # BUG under test: should be unconditional — the tail slab
        # (ki == nk - 1) is never waited.
        @pl.when(ki + 1 < nk)
        def _wait_current():
            for c in slab_copies(ki, ki % 2):
                c.wait()

        w_blk = w_ref[...]
        slot = ki % 2
        for j in range(k_slab):
            acc_ref[0] += w_blk[:, j:j + 1] \
                * rows_ref[slot, j].astype(jnp.float32)

        @pl.when(ki == nk - 1)
        def _flush():
            out_ref[...] = acc_ref[0].astype(out_ref.dtype)

    return kernel


# ---------------------------------------------------------------------------
# jaxpr: closure-captured host constant / f64 widening
# ---------------------------------------------------------------------------

#: bytes of the captured table — comfortably past HOST_CONST_BYTES
CAPTURED_TABLE_ELEMS = 4096


def make_constant_capture_fn():
    """-> (fn, example_arg): ``fn`` closes over a 16 KiB host
    ``np.ndarray`` that tracing folds into ``closed.consts`` — the
    jaxpr checker must report the baked HLO literal."""
    table = np.arange(CAPTURED_TABLE_ELEMS, dtype=np.float32)

    def step(x):
        return x * 2.0 + table

    return step, jnp.ones(CAPTURED_TABLE_ELEMS, jnp.float32)


def make_f64_fn():
    """-> (fn, example_arg): widens to float64.  Trace under
    ``jax.enable_x64(True)`` so the widening survives into
    the jaxpr instead of being silently clamped to f32."""

    def f(x):
        return jnp.asarray(x, jnp.float64) * 2.0

    return f, np.ones(8, np.float32)


# ---------------------------------------------------------------------------
# thread: shared attribute written from both sides
# ---------------------------------------------------------------------------

#: a worker thread and the main thread both rebind ``self.count``
#: without any lock/queue discipline — the thread checker must emit an
#: error for ``fixture_mod.LossyCounter.count``
BROKEN_THREAD_SRC = '''\
import threading


class LossyCounter:
    def __init__(self):
        self._thread = None
        self.count = 0

    def start(self):
        self._thread = threading.Thread(target=self._run)
        self._thread.start()

    def _run(self):
        while True:
            self.count = self.count + 1

    def reset(self):
        self.count = 0
'''


# ---------------------------------------------------------------------------
# runners — shared by scripts/analyze.py --fixture and the tests
# ---------------------------------------------------------------------------

def run_fixture(name: str):
    """Run one seeded-broken fixture through its checker.
    -> list[Finding]; the caller asserts/gates on non-emptiness."""
    from repro.analysis import pallas_audit, thread_audit
    from repro.analysis.jaxpr_audit import _walk_hazards

    if name == "dma":
        return pallas_audit.simulate_dma_pairing(
            make_unmatched_wait_kernel, nk=3,
            site="fixture:unmatched_wait")
    if name == "constant":
        import jax
        fn, arg = make_constant_capture_fn()
        return _walk_hazards(jax.make_jaxpr(fn)(arg), "fixture:constant")
    if name == "f64":
        import jax
        fn, arg = make_f64_fn()
        with jax.enable_x64(True):
            closed = jax.make_jaxpr(fn)(arg)
        return _walk_hazards(closed, "fixture:f64")
    if name == "thread":
        return thread_audit.analyze_source(BROKEN_THREAD_SRC,
                                           "fixture_mod")
    raise ValueError(f"unknown fixture {name!r} "
                     "(expected dma|constant|f64|thread)")


FIXTURES = ("dma", "constant", "f64", "thread")
