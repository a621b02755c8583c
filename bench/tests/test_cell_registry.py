"""A configuration, a traffic mix and a per-layer metric are found by the
names BENCHMARK.json gives them: adding a cell is adding files and entries,
with no edit to a file that is there."""

import json
from types import SimpleNamespace

from tiny_root import ROOT, run, run_tiny


def _add_cell(root):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/graph500-22.json").read_text())
    (root / "bench/configs").mkdir(parents=True)
    (root / "bench/traffic").mkdir()
    (root / "bench/metrics").mkdir()
    (root / "bench/configs/graph500-9.json").write_text(
        json.dumps(dict(cfg, name="graph500-9", scale=9)))
    (root / "bench/traffic/pagerank-2.json").write_text(json.dumps(
        {"job": "pagerank", "iterations": 2, "damping": 0.85}))
    (root / "bench/metrics/window.iterations.py").write_text(
        "def read(ctx):\n    return float(ctx.iterations)\n")
    spec["configs"].append({"name": "graph500-9", "source": "test",
                            "file": "bench/configs/graph500-9.json",
                            "reduced": ["scale"], "why": "test"})
    spec["workloads"].append({"name": "pagerank-2.graph500-9",
                              "config": "graph500-9",
                              "traffic": "pagerank-2", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "window.iterations", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "host driver", "moves": "iteration_s",
                              "workloads": ["pagerank-2.graph500-9"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def test_new_files_are_found_by_name(tmp_path):
    before = {p: p.read_bytes() for p in (ROOT / "bench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    _add_cell(tmp_path)
    parts = run.resolve(tmp_path, run.load_spec(tmp_path),
                        "pagerank-2.graph500-9")
    assert parts.cfg["scale"] == 9
    assert parts.traffic["iterations"] == 2
    assert parts.job.__file__ == str(ROOT / "bench/jobs/pagerank.py")
    assert "window.iterations" in parts.readers
    assert "segment_combine_roofline" not in parts.readers
    reader = parts.readers["window.iterations"]
    assert reader.read(SimpleNamespace(iterations=6)) == 6.0
    out = run_tiny(tmp_path, "pagerank-2.graph500-9")
    assert out["correct"] and out["checks"]["iterations_off"]["value"] == 0
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_every_name_in_the_benchmark_resolves():
    spec = run.load_spec(ROOT)
    for cell in spec["workloads"]:
        parts = run.resolve(ROOT, spec, cell["name"])
        assert parts.cfg["name"] == cell["config"]
        assert {m["name"] for m in parts.e2e} == {"iteration_s", "setup_s"}
        for m in spec["per_layer"]:
            assert (m["name"] in parts.readers) == (
                cell["name"] in m["workloads"])


def test_the_ppr_query_is_one_vertex_of_the_dataset_under_every_seed():
    import graph500
    import numpy as np

    parts = run.resolve(run.ROOT, run.load_spec(run.ROOT),
                        "ppr-rowtable.graph500-20")
    cfg = dict(parts.cfg, scale=10)
    sources = set()
    for seed in (3, 2**31 + 9, 2**40 + 1):
        inputs = parts.job.build(cfg, parts.traffic, seed)
        (vertex,) = inputs["seeds"]
        assert inputs["outdeg"][vertex] >= 1
        sources.add(int(np.argsort(graph500.labels(cfg, seed))[vertex]))
    assert len(sources) == 1
