"""Configurations, mixes and per-layer metrics are found by name from files
alone, and adding one is adding files and entries: no existing file of the
benchmark changes."""
import hashlib
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from kbench import discover, tiny  # noqa: E402


def _digests(root: Path) -> dict:
    return {p.relative_to(root).as_posix():
            hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_discovery_from_a_directory_of_one_of_each(tmp_path):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    (tmp_path / "bench" / "configs" / "c1.json").write_text('{"size": 3}')
    (tmp_path / "bench" / "traffic" / "t1.json").write_text('{"rate": 7}')
    (tmp_path / "bench" / "metrics" / "m.one.py").write_text(
        "def read(ctx):\n    return ctx * 2\n")
    bench = {
        "configs": [{"name": "c1", "file": "bench/configs/c1.json"}],
        "workloads": [{"name": "c1.t1", "config": "c1", "traffic": "t1"}],
        "end_to_end": [{"name": "setup_s"},
                       {"name": "rate", "workloads": ["other"]}],
        "per_layer": [{"name": "m.one", "moves": "setup_s"}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    b = discover.load_benchmark(tmp_path)
    cell = discover.workload(b, "c1.t1")
    assert discover.config(tmp_path, b, cell["config"]) == {"size": 3}
    assert discover.traffic(tmp_path / "bench", cell["traffic"]) == {
        "rate": 7}
    assert [m["name"] for m in discover.end_to_end_for(b, "c1.t1")] == [
        "setup_s"]
    metrics = discover.per_layer_for(b, "c1.t1")
    assert [m["name"] for m in metrics] == ["m.one"]
    assert discover.metric_reader(tmp_path / "bench", "m.one").read(21) == 42


def test_a_new_metric_mix_and_config_need_no_edit(tmp_path):
    root = tiny.make_root(tmp_path)
    before = _digests(root)
    # new files only: a configuration, a mix and a per-layer metric
    cfg = dict(tiny.CONFIG, name="tiny2")
    (root / "bench" / "configs" / "tiny2.json").write_text(json.dumps(cfg))
    mix = dict(tiny.INGEST, publish_policy="every:2")
    (root / "bench" / "traffic" / "every2.json").write_text(json.dumps(mix))
    (root / "bench" / "metrics" / "published_epochs.py").write_text(
        "def read(ctx):\n"
        "    w = ctx.window\n"
        "    return sum(1 for p in w.publishes if w.t_open <= p[0] < w.t_close)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny2", "source": "test",
                             "file": "bench/configs/tiny2.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny2.every2", "config": "tiny2",
                               "traffic": "every2", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "published_epochs", "unit": "epochs",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "serving snapshot",
                               "moves": "ingest_edges_per_s",
                               "workloads": ["tiny2.every2"]})
    bench["end_to_end"][1]["workloads"].append("tiny2.every2")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = _digests(root)
    assert {k: v for k, v in after.items() if k in before} == before
    out = tiny.run_cell(root, "tiny2.every2", seed=2 ** 40 + 1, trace=1)
    assert out["correct"], out
    assert out["metrics"]["published_epochs"]["value"] > 0
    assert set(out["metrics"]) == {"published_epochs"}
