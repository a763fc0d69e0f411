"""A run with the timed path broken underneath reads ``correct`` false: once
for each fault a cell can have (``bench/control.py`` plants them).  The
harness's look for a chip is skipped (``require_tpu=False``); everything else
is the run as the chip makes it.  The cells run on one chip, so an exchange
between chips cannot be left out.
"""
import importlib.util
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from kbench import tiny  # noqa: E402


def _variants():
    spec = importlib.util.spec_from_file_location("kbench_control",
                                                  HERE / "control.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.VARIANTS


@pytest.mark.parametrize("fault,workload,check", [
    ("state_unchanged", "tiny.ingest", "counter_mismatch_cells"),
    ("half_batch_left_out", "tiny.ingest", "counter_mismatch_cells"),
    ("answer_off_by_one", "tiny.serve", "wrong_answers"),
])
def test_fault_reads_not_correct(tmp_path, monkeypatch, fault, workload,
                                 check):
    _variants()[fault](monkeypatch.setattr)
    out = tiny.run_cell(tiny.make_root(tmp_path), workload,
                        seed=2 ** 35 + 17)
    assert not out["correct"]
    assert out["checks"][check]["value"] > 0
