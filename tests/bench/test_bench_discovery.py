"""A new configuration, model, kernel, traffic mix or per-layer metric is a
new file the harness finds by the name BENCHMARK.json (or a
configuration's ``arch``) gives it: no edit of the harness."""
import json

import pytest

from perfbench import harness, readers, trace
from perfbench.trace import Trace


def _checkout(tmp_path, arch="newarch"):
    (tmp_path / "perfbench" / "traffic").mkdir(parents=True)
    (tmp_path / "perfbench" / "configs").mkdir()
    (tmp_path / "perfbench" / "configs" / "newcfg.json").write_text(
        json.dumps({"name": "newcfg", "arch": arch, "num_nodes": 7}))
    (tmp_path / "perfbench" / "traffic" / "newmix.json").write_text(
        json.dumps({"kind": "fullgraph", "full_graph_iters": 2}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "newcfg", "file": "perfbench/configs/newcfg.json"}],
        "workloads": [{"name": "new-cell", "config": "newcfg",
                       "traffic": "newmix", "chips": 4}]}))
    models = tmp_path / "models"
    models.mkdir()
    (models / "newarch.py").write_text("NAME = 'a new model'\n")
    return models


def test_new_config_traffic_and_metric_found_by_name(tmp_path):
    models = _checkout(tmp_path)
    spec = harness.load_cell("new-cell", tmp_path, models=models)
    assert spec.config["num_nodes"] == 7
    assert spec.traffic["full_graph_iters"] == 2 and spec.chips == 4
    assert spec.model.NAME == "a new model"

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


def test_config_naming_an_arch_without_module_fails_naming_the_path(tmp_path):
    models = _checkout(tmp_path, arch="gat")
    with pytest.raises(FileNotFoundError) as err:
        harness.load_cell("new-cell", tmp_path, models=models)
    assert str(models / "gat.py") in str(err.value)


def test_new_kernel_file_tags_its_ops(tmp_path):
    """A kernel file claims a Pallas op by its HLO text; the first file
    (by name) that matches tags it, and an op no file claims reads
    ``[pallas]``."""
    (tmp_path / "newkernel.py").write_text(
        "def matches(hlo):\n    return 'f32[4096,128]' in hlo\n")
    kernels = {**trace.load_kernels(), **trace.load_kernels(tmp_path)}
    op = ('%closed_call.5 = f32[4096,128]{1,0} custom-call('
          'f32[4096,128]{1,0} %p0), custom_call_target="tpu_custom_call"')
    assert trace.short_name(op, kernels).endswith(" [newkernel]")
    assert trace.short_name(op).endswith(trace.OTHER_KERNEL)


def test_every_declared_metric_and_cell_has_its_files():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for m in bench["per_layer"]:
        assert (readers.METRICS / f"{m['name']}.py").exists(), m["name"]
    for w in bench["workloads"]:
        spec = harness.load_cell(w["name"])
        assert spec.traffic["kind"] in ("sampled", "fullgraph")
        assert (harness.BENCH / "limits" / f"{w['name']}.json").exists()
        for fn in ("layer_dims", "init_params", "build_program",
                   "to_program_params", "from_program_params",
                   "sampled_logits", "full_logits", "rows_logits",
                   "train_flops"):
            assert callable(getattr(spec.model, fn)), (w["name"], fn)
