"""Configurations, cells and per-layer metrics are found by name, from
files: adding one is adding its file and its entry in BENCHMARK.json."""
import json
import shutil

import pytest

from conftest import BENCH, ROOT


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_entry_has_its_files():
    import spec
    bench = _bench()
    for c in bench["configs"]:
        cfg = spec.load_json(ROOT / c["file"])
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert cell.limits


def test_metric_files_agree_with_their_entries():
    import spec
    for m in _bench()["per_layer"]:
        mod = spec.load_reader(m["name"])
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == \
            (m["unit"], m["layer"], m["moves"], m["source"]), m["name"]
        assert callable(mod.read)


def test_cell_gets_only_its_metrics():
    import spec
    bench = _bench()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        want = [m["name"] for m in bench["per_layer"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert [m.name for m in cell.per_layer] == want


def _copy_checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / BENCH.relative_to(ROOT),
                    ignore=shutil.ignore_patterns(".runs", "__pycache__"))
    return root


def _snapshot(root):
    return {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
            if p.is_file()}


def test_new_traffic_on_existing_config_edits_no_file(tmp_path):
    """A new traffic mix on a configuration that is already there is a
    traffic file, a limits file and a BENCHMARK.json entry: the
    configuration's file is left as it is."""
    import spec
    root = _copy_checkout(tmp_path)
    before = _snapshot(root)
    bench = _bench()
    w0 = bench["workloads"][0]
    tr = json.loads((BENCH / "traffic" / f"{w0['traffic']}.json")
                    .read_text())
    tr.update(reads=262144, inserts=786432)
    (root / "benchmarks/chip/traffic/new-traffic.json").write_text(
        json.dumps(tr))
    (root / "benchmarks/chip/limits/new-cell.json").write_text(
        json.dumps({"gap_max": ["<=", 0.5]}))
    bench["workloads"].append({"name": "new-cell", "config": w0["config"],
                               "traffic": "new-traffic", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("new-cell", root=root)
    assert cell.config == spec.load_cell(w0["name"]).config
    assert cell.traffic["inserts"] == 786432
    assert cell.limits == {"gap_max": ["<=", 0.5]}
    after = _snapshot(root)
    assert all(after[p] == b for p, b in before.items())


def test_new_files_are_found_without_editing_any(tmp_path):
    """A copy of the benchmark gains a configuration, a traffic mix, a
    cell and a metric: new files plus new entries, no file edited."""
    import spec
    root = _copy_checkout(tmp_path)
    before = _snapshot(root)
    bench = _bench()
    cfg = json.loads((ROOT / bench["configs"][0]["file"]).read_text())
    cfg["name"] = "new-config"
    (root / "benchmarks/chip/configs/new-config.json").write_text(
        json.dumps(cfg))
    tr = json.loads((BENCH / "traffic" /
                     f"{bench['workloads'][0]['traffic']}.json").read_text())
    tr["outstanding"] = 16
    (root / "benchmarks/chip/traffic/new-traffic.json").write_text(
        json.dumps(tr))
    (root / "benchmarks/chip/limits/new-cell.json").write_text(
        json.dumps({"gap_median": ["<=", 1.0]}))
    (root / "benchmarks/chip/metrics/new_metric.py").write_text(
        'UNIT = "count"\nLAYER = "pool tick"\nMOVES = "tune_steps_per_s"\n'
        'SOURCE = "program_counter"\n\n\ndef read(ctx):\n'
        '    return float(ctx.counters["ticks"])\n')
    bench["configs"].append(dict(bench["configs"][0], name="new-config",
                                 file="benchmarks/chip/configs/"
                                      "new-config.json"))
    bench["workloads"].append({"name": "new-cell", "config": "new-config",
                               "traffic": "new-traffic", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "new_metric", "unit": "count",
                               "better": "lower",
                               "source": "program_counter",
                               "layer": "pool tick",
                               "moves": "tune_steps_per_s",
                               "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("new-cell", root=root)
    assert cell.config["name"] == "new-config"
    assert cell.traffic["outstanding"] == 16
    assert cell.limits == {"gap_median": ["<=", 1.0]}
    assert [m.name for m in cell.per_layer] == ["new_metric"]
    after = _snapshot(root)
    assert all(after[p] == b for p, b in before.items())

    class Ctx:
        counters = {"ticks": 7}
    assert cell.per_layer[0].read(Ctx()) == 7.0
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell", root=root)
