"""BENCHMARK.json names only what exists, and keeps to the shape the check
reads."""
import json
import re

from bench import harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_name_resolves_to_its_file():
    bench = harness.BENCH
    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert (harness.ROOT / c["file"]).is_file()
        assert c["file"] == f"bench/configs/{c['name']}.json"
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert (bench / "models" / f"{cfg['kind']}.py").is_file()
        assert len(cfg["tables"]["rows"]) == len(cfg["tables"]["seq"])
    for w in SPEC["workloads"]:
        assert w["config"] in configs
        assert (bench / "traffic" / f"{w['traffic']}.json").is_file()
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"] == json.loads(
            (harness.ROOT / configs[w["config"]]["file"]).read_text())["chips"]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (bench / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_names_units_and_coverage():
    cells = [w["name"] for w in SPEC["workloads"]]
    names = cells + [c["name"] for c in SPEC["configs"]] + [
        m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", cells)
    for cell in cells:
        mine = [m for m in e2e.values() if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(cell in m["workloads"] for m in SPEC["per_layer"])


def test_no_executor_option_in_a_benchmark_file():
    knobs = ("access", "kernel_path", "layout", "use_kernels", "reduce_mode",
             "hardware_options", "degrade_after")
    for f in list((harness.BENCH / "configs").glob("*.json")) + list(
            (harness.BENCH / "traffic").glob("*.json")):
        assert not set(json.loads(f.read_text())) & set(knobs), f.name
