import json
import shutil

import pytest

from benchmark import manifest


@pytest.mark.parametrize("name", ["drift8v.steady", "drift4c.steady"])
def test_each_cell_resolves_its_files_by_name(name):
    cell = manifest.resolve(name)
    man = manifest.load_manifest()
    entry = {w["name"]: w for w in man["workloads"]}[name]
    assert cell.config["name"] == entry["config"]
    assert cell.traffic["driver"] == "drift_loop"
    assert hasattr(cell.driver, "build")
    assert {m["name"] for m in cell.end_to_end} == {
        "particles_per_s_per_chip", "call_p95_ms", "setup_s"}
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cell.readers[m["name"]].read)
    layer = {m["name"] for m in cell.per_layer}
    assert {"device_idle_pct", "landing_roofline", "plan_ms_per_step",
            "exchange_bytes_per_step"} <= layer
    assert ("driftbin_roofline" in layer) == (name == "drift8v.steady")
    assert ("exchange_ms_per_step" in layer) == (name == "drift4c.steady")


def test_every_manifest_name_has_its_file():
    man = manifest.load_manifest()
    for c in man["configs"]:
        assert (manifest.ROOT / c["file"]).is_file()
    for w in man["workloads"]:
        assert (manifest.BENCH_DIR / "traffic" / f"{w['traffic']}.json").is_file()
    for m in man["end_to_end"] + man["per_layer"]:
        assert (manifest.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()


def test_a_new_traffic_and_metric_need_no_edit(tmp_path):
    """A later cell adds files and entries; no existing file changes."""
    bench = tmp_path / "benchmark"
    shutil.copytree(manifest.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p.relative_to(bench): p.read_bytes()
              for p in bench.rglob("*") if p.is_file()}
    man = manifest.load_manifest()
    (bench / "traffic" / "burst.json").write_text(json.dumps(
        {"driver": "drift_loop", "steps_per_call": 64}))
    (bench / "metrics" / "calls_per_window.py").write_text(
        "def read(run):\n    return float(run.calls)\n")
    man["workloads"].append({"name": "drift8v.burst", "config": "grid222-8v",
                             "traffic": "burst", "chips": 1, "why": "x"})
    man["per_layer"].append({"name": "calls_per_window", "unit": "calls",
                             "better": "higher", "source": "host_clock",
                             "layer": "entry",
                             "moves": "particles_per_s_per_chip"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))

    cell = manifest.resolve("drift8v.burst", root=tmp_path, bench_dir=bench)
    assert cell.traffic["steps_per_call"] == 64
    assert "calls_per_window" in cell.readers
    assert cell.readers["calls_per_window"].read(type("R", (), {"calls": 7})) == 7.0
    # without a workloads key, the metric reaches every cell that reports
    # the end-to-end metric it moves, the existing cells among them
    old = manifest.resolve("drift8v.steady", root=tmp_path, bench_dir=bench)
    assert "calls_per_window" in old.readers
    for rel, data in before.items():
        assert (bench / rel).read_bytes() == data


def test_an_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        manifest.resolve("no.such.cell")
