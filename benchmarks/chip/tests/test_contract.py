"""BENCHMARK.json and the files it names fit together.

Every cell names a configuration and a traffic mix that exist, every
per-layer metric has a reader under ``metrics/``, and every name keeps
to the characters a name may have.
"""
import importlib.util
import json
import pathlib
import re

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names


def test_cells_find_their_files():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for cell in BENCH["workloads"]:
        cfg = configs[cell["config"]]
        assert json.loads((ROOT / cfg["file"]).read_text())["name"] \
            == cfg["name"]
        traffic = HERE / "traffic" / f"{cell['traffic']}.json"
        assert json.loads(traffic.read_text())["workload"]["kind"]
        assert cell["chips"] in (1, 4)


def test_every_metric_has_a_reader():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        path = HERE / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(m["name"], path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert callable(mod.read)


def test_every_cell_reports_a_layer_metric():
    for cell in BENCH["workloads"]:
        assert any(cell["name"] in m.get("workloads", [cell["name"]])
                   for m in BENCH["per_layer"])
