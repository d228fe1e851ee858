"""A configuration, traffic mix, limit or metric added as a new file is
found by its name; nothing else needs an edit."""
import json

import pytest

from benchlib import harness, spec


def test_new_cell_from_new_files(tree):
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-new", "config": "tiny-new",
                               "traffic": "new-mix", "chips": 1,
                               "why": "test"})
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))
    conf = json.loads((tree / "configs" / "tiny.json").read_text())
    conf["latent"] = [2, 8, 8]
    (tree / "configs" / "tiny-new.json").write_text(json.dumps(conf))
    mix = json.loads((tree / "workloads" / "lp2-3step-closed.json")
                     .read_text())
    mix["steps_per_request"] = 6
    (tree / "workloads" / "new-mix.json").write_text(json.dumps(mix))
    cell = harness.load_cell("tiny-new", 3, root=tree, base=tree)
    assert cell.latent == (2, 8, 8) and cell.steps == 6 and cell.chips == 1


def test_new_metric_reader_found_by_name(tree):
    (tree / "metrics" / "x.new_metric.py").write_text(
        "def read(rec):\n    return 2 * rec['steps']\n")
    assert spec.reader("x.new_metric", tree)({"steps": 21}) == 42


def test_metric_lists_follow_the_workloads_key(tree):
    bench = spec.benchmark(tree)
    e2e = [m["name"] for m in spec.metrics_for(bench, "tiny-lp2", False)]
    assert e2e == ["step_s", "setup_s"]
    layer = [m["name"] for m in spec.metrics_for(bench, "tiny-lp2", True)]
    assert "device.idle_share" in layer and "step.mfu" in layer


def test_unknown_cell_is_an_error(tree):
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell", 1, root=tree, base=tree)


def test_limits_found_by_cell_name(tree):
    assert spec.limits("tiny-lp2", tree)["served_err"]["limit"] == 1e-3
    assert spec.limits("nothing", tree) is None
