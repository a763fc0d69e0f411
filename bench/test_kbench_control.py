"""The control of the output check: the same run with the ingest step fed in
bfloat16 must read ``correct`` false, while the run as it stands reads true.
At this size an 8192-edge client batch of the tiny graph dedups to rows with
weights above 256 that bfloat16 cannot hold."""
import importlib.util
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from kbench import tiny  # noqa: E402
from kbench.stream import Lap  # noqa: E402

INGEST_8K = dict(tiny.INGEST, client_batch=8192)


def _control():
    spec = importlib.util.spec_from_file_location("kbench_control",
                                                  HERE / "control.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_batches_carry_weights_bfloat16_rounds():
    lap = Lap(tiny.CONFIG["graph"], 8192, seed=7, client_batch=8192)
    src, dst, _ = lap.client_batch_numpy(0)
    key = (src.astype(np.int64) << 32) | dst
    _, counts = np.unique(key, return_counts=True)
    import jax.numpy as jnp

    rounded = counts.astype(jnp.bfloat16).astype(np.int64)
    assert (rounded != counts).any()


def test_control_reads_not_correct(tmp_path, monkeypatch):
    from repro.serving import snapshot

    root = tiny.make_root(tmp_path, ingest=INGEST_8K)
    monkeypatch.setattr(snapshot, "_KERNELS", {})
    sound = tiny.run_cell(root, "tiny.ingest", seed=31)
    assert sound["correct"], sound["checks"]
    _control().apply_control(monkeypatch.setattr)
    out = tiny.run_cell(root, "tiny.ingest", seed=31)
    assert not out["correct"]
    assert out["checks"]["counter_mismatch_cells"]["value"] > 0
    assert out["checks"]["unpublished_edges"]["value"] == 0
