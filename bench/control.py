#!/usr/bin/env python3
"""The control of the output check: the ingest step in bfloat16.

The configuration states exact int32 counters.  The step that would tempt a
later change is to feed the ingest contraction in bfloat16 (one MXU pass
instead of the about six that ``Precision.HIGHEST`` takes): the one-hot
operands stay exact, but every weight above 256 that is not a multiple of
its bfloat16 spacing is rounded.  After exact duplicate-edge pre-aggregation
a client batch carries such weights, so the counters must come out wrong and
``correct`` false.  ``Precision.HIGH`` (three bf16 passes) would keep weights
below 2^16 exact and is not a control.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

runs the cell exactly as ``bench/run.py`` does with the control patched in:
the Pallas ingest kernel's operands cast to bfloat16 (the width-class layout)
and the flat scatter's weights rounded through bfloat16 (the flat layout),
whichever the platform default picks.  ``--variant`` plants one of the
faults a cell can have instead (see ``VARIANTS``).  The benchmark's own runs
never do this.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def _bf16_kernel(hi_ref, hj_ref, wt_ref, pool_ref, out_ref):
    """``matrix_ingest``'s kernel body with its operands in bfloat16."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(3) == 0)
    def _init():
        out_ref[...] = pool_ref[...]

    tr, w = out_ref.shape[-2:]
    tb = hi_ref.shape[-1]
    row0 = pl.program_id(2) * tr
    hi = hi_ref[0, 0]
    hj = hj_ref[0, 0]
    wt = wt_ref[0].astype(jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (tr, tb), 0) + row0
    cols = jax.lax.broadcasted_iota(jnp.int32, (w, tb), 0)
    u_t = (rows == hi).astype(jnp.bfloat16)
    v_t = jnp.where(cols == hj, wt, 0.0).astype(jnp.bfloat16)
    inc = jax.lax.dot_general(u_t, v_t, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    out_ref[0, 0] += inc.astype(out_ref.dtype)


def apply_control(patch=setattr) -> None:
    """Patch the program's ingest paths to bfloat16 weights.  Call before
    the first ingest is traced in the process; ``patch`` is ``setattr`` or
    a test's undoable equivalent."""
    import jax.numpy as jnp
    from repro.core import kmatrix
    from repro.serving import snapshot

    # the package re-exports the jitted function under the module's name
    matrix_ingest = importlib.import_module("repro.kernels.matrix_ingest")
    flat_ingest = kmatrix.ingest

    def bf16_flat_ingest(sk, batch):
        w = batch.weight.astype(jnp.bfloat16).astype(batch.weight.dtype)
        return flat_ingest(sk, batch.replace(weight=w))

    patch(kmatrix, "ingest", bf16_flat_ingest)
    patch(matrix_ingest, "_ingest_kernel", _bf16_kernel)
    patch(snapshot, "_KERNELS", {})


# Faults a cell can have, planted where the work is produced.  The tests
# (``test_kbench_faults.py``) run each on the CPU; on the chip they are run
# by hand as ``--variant <name>``.

def _sketch_modules():
    from repro.core import kmatrix, kmatrix_accel

    return kmatrix, kmatrix_accel


def state_unchanged(patch=setattr) -> None:
    """The ingest step returns the sketch it was given."""
    from repro.serving import snapshot

    for mod in _sketch_modules():
        patch(mod, "ingest", lambda sk, batch, **_: sk)
    patch(snapshot, "_KERNELS", {})


def half_batch_left_out(patch=setattr) -> None:
    """The ingest step drops the second half of every dispatched batch."""
    from repro.serving import snapshot

    for mod in _sketch_modules():
        def half(sk, batch, _ingest=mod.ingest, **kw):
            n = batch.weight.shape[0]
            w = batch.weight.at[n // 2:].set(0)
            return _ingest(sk, batch.replace(weight=w), **kw)

        patch(mod, "ingest", half)
    patch(snapshot, "_KERNELS", {})


def answer_off_by_one(patch=setattr) -> None:
    """The engine adds one to every edge-frequency answer it produces."""
    from repro.serving.engine import EDGE_FREQ, QueryEngine

    handler = QueryEngine._HANDLERS[EDGE_FREQ]

    def off_by_one(self, snapshot, sk, mod, key, idxs, requests, values):
        handler(self, snapshot, sk, mod, key, idxs, requests, values)
        for i in idxs:
            values[i] += 1

    handlers = dict(QueryEngine._HANDLERS, **{EDGE_FREQ: off_by_one})
    patch(QueryEngine, "_HANDLERS", handlers)


VARIANTS = {"bf16": apply_control, "state_unchanged": state_unchanged,
            "half_batch_left_out": half_batch_left_out,
            "answer_off_by_one": answer_off_by_one}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--variant", choices=sorted(VARIANTS), default="bf16")
    known, rest = ap.parse_known_args(argv)
    VARIANTS[known.variant]()
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  BENCH / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
