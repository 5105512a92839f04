"""A new configuration, traffic mix or per-layer metric is a new file the
harness finds by the name BENCHMARK.json gives it: no edit of the harness."""
import json

from perfbench import harness, readers
from perfbench.trace import Trace


def test_new_config_traffic_and_metric_found_by_name(tmp_path):
    (tmp_path / "perfbench" / "traffic").mkdir(parents=True)
    (tmp_path / "perfbench" / "configs").mkdir()
    (tmp_path / "perfbench" / "configs" / "newcfg.json").write_text(
        json.dumps({"name": "newcfg", "num_nodes": 7}))
    (tmp_path / "perfbench" / "traffic" / "newmix.json").write_text(
        json.dumps({"kind": "fullgraph", "full_graph_iters": 2}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "newcfg", "file": "perfbench/configs/newcfg.json"}],
        "workloads": [{"name": "new-cell", "config": "newcfg",
                       "traffic": "newmix", "chips": 4}]}))
    spec = harness.load_cell("new-cell", tmp_path)
    assert spec.config["num_nodes"] == 7
    assert spec.traffic["full_graph_iters"] == 2 and spec.chips == 4

    metrics = tmp_path / "metrics"
    metrics.mkdir()
    (metrics / "newlayer.count.py").write_text(
        "def read(ctx):\n    return len(ctx.trace.devices)\n")
    (metrics / "newlayer.silent.py").write_text(
        "def read(ctx):\n    return None\n")
    ctx = readers.Context(trace=Trace(window=(0, 1), devices={0: []}),
                          dev=0, chips=1, peaks={}, kind="fullgraph",
                          dims=(1, 1), fanouts=(), epochs=[], owned=[],
                          halo=[], edges=[])
    entries = [{"name": "newlayer.count", "unit": "n"},
               {"name": "newlayer.silent", "unit": "%"}]
    assert readers.read_all(entries, ctx, root=metrics) == {
        "newlayer.count": {"value": 1.0, "unit": "n"}}


def test_every_declared_metric_and_cell_has_its_files():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for m in bench["per_layer"]:
        assert (readers.METRICS / f"{m['name']}.py").exists(), m["name"]
    for w in bench["workloads"]:
        spec = harness.load_cell(w["name"])
        assert spec.traffic["kind"] in ("sampled", "fullgraph")
        assert (harness.BENCH / "limits" / f"{w['name']}.json").exists()
