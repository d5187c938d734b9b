"""A cell, a configuration, a traffic mix and a metric added as files are
found by name, with no edit to any file already there: a builder that a
cell file names and a generator that a traffic file names too."""
import json
import shutil

from bench import spec


def test_new_files_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(spec.BENCH_DIR, bench, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    manifest = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    (bench / "configs" / "new_cfg.json").write_text(json.dumps({"name": "new_cfg", "d_model": 8}))
    (bench / "traffic" / "new_mix.json").write_text(json.dumps({"generator": "new_gen"}))
    (bench / "generators" / "new_gen.py").write_text(
        "def make_pool(key, traffic, vocab):\n    return [vocab]\n")
    (bench / "cells" / "new.cell.json").write_text(json.dumps({"builder": "new_builder"}))
    (bench / "builders" / "new_builder.py").write_text(
        "def build(cell, key):\n    return cell.name, key\n")
    (bench / "metrics" / "new_metric.py").write_text("def read(ctx):\n    return 42.0\n")
    manifest["configs"].append({"name": "new_cfg", "file": "bench/configs/new_cfg.json"})
    manifest["workloads"].append({"name": "new.cell", "config": "new_cfg",
                                  "traffic": "new_mix", "chips": 1, "why": "test"})
    manifest["per_layer"].append({"name": "new_metric", "unit": "%", "workloads": ["new.cell"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))

    cell = spec.load_cell("new.cell", bench, path)
    assert cell.options == {"builder": "new_builder"}
    assert cell.config["d_model"] == 8
    assert spec.builder(cell, bench).build(cell, 7) == ("new.cell", 7)
    assert spec.generator(cell.traffic, bench).make_pool(None, cell.traffic, 5) == [5]
    names = [m["name"] for m in cell.per_layer]
    assert "new_metric" in names
    assert spec.metric_reader("new_metric", bench)(None) == 42.0
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_every_benchmark_name_has_its_files():
    manifest = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    for w in manifest["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert callable(spec.builder(cell).build)
        assert callable(spec.generator(cell.traffic).make_pool)
        spec.reference_module(cell.config)
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))
    for c in manifest["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
