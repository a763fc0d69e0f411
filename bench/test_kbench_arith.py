"""The benchmark's arithmetic: percentiles, rates, freshness,
update bytes, roofline shares and the peaks table."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from kbench import stats  # noqa: E402


def test_percentile_interpolates_and_handles_empty():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile(list(range(101)), 95) == 95.0
    assert np.isnan(stats.percentile([], 90))


def test_rate_counts_edges_made_queryable_in_the_window():
    pubs = [(0.5, 100, 1), (1.5, 300, 2), (2.5, 700, 3)]
    assert stats.visible_edges(pubs, 0.4) == 0
    assert stats.visible_edges(pubs, 1.5) == 300
    # visible at t=1.0: 100; at t=3.0: 700
    assert stats.rate_over(pubs, 1.0, 3.0) == pytest.approx(300.0)


def test_visibility_is_first_publish_covering_the_batch():
    pubs = [(1.0, 4096, 1), (2.0, 12288, 2)]
    assert stats.visibility_times(pubs, [4096, 8192, 12288, 16384]) == [
        1.0, 2.0, 2.0, None]


def test_update_bytes_and_roofline_share():
    # 8192 raw rows, 4000 distinct pairs, depth 7
    b = stats.ingest_update_bytes(8192, 4000, 7)
    assert b == 12 * 8192 + 16 * 7 * 4000
    assert stats.roofline_share(1.0, 4.0) == 25.0
    assert stats.roofline_share(1.0, 0.0) is None


def test_distinct_pairs_counts_ordered_pairs():
    src = np.array([1, 1, 2, 2, 1], np.int32)
    dst = np.array([2, 2, 1, 3, 2], np.int32)
    assert stats.distinct_pairs(src, dst) == 3


def test_peaks_lookup_knows_v5e_and_refuses_unknown(tmp_path):
    v5e = stats.load_peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        stats.load_peaks("TPU v9 imaginary")
    table = tmp_path / "peaks.json"
    table.write_text(json.dumps({"devices": {"X": {"hbm_bytes_per_s": 1}}}))
    assert stats.load_peaks("X", table) == {"hbm_bytes_per_s": 1}
