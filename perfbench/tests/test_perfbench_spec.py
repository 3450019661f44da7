"""Cells, configurations, mixes, limits and metric readers are data found
by name: adding a cell touches no file that is already there."""
import ast
import json
import shutil
import sys
import types
from pathlib import Path

import pytest

from perfbench.harness import spec

ROOT = Path(__file__).resolve().parents[2]
NUMBERS = {"live": {"mismatch_rounds_pct", "spend_gap", "capacity_gap",
                    "slot_faults"},
           "sweep": {"mismatch_rounds_pct", "capacity_gap",
                     "efficiency_gap", "fairness_gap", "leftover_gap",
                     "slot_faults", "repeat_faults"}}


def test_every_cell_resolves():
    bench = spec.benchmark(ROOT)
    for w in bench["workloads"]:
        cell = spec.cell(w["name"], bench)
        assert cell["config"]["name"] == w["config"]
        assert cell["traffic"]["name"] == w["traffic"]
        assert cell["limits"]
        assert set(cell["limits"]) <= NUMBERS[cell["traffic"]["entry"]]
        assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
        assert cell["per_layer"]
    for m in bench["per_layer"]:
        assert callable(spec.reader(m["name"]))


def test_cell_added_as_data_is_found(tmp_path):
    """A new config, mix and limits file plus a ``workloads`` entry: the
    harness finds the cell without an edit of an existing file."""
    base = tmp_path / "perfbench"
    shutil.copytree(ROOT / "perfbench", base,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((base / "configs" / "paper_vi.json").read_text())
    cfg["name"] = "paper_vi_deep"
    cfg["deployment"]["p_ten_blocks"] = 0.75
    (base / "configs" / "paper_vi_deep.json").write_text(json.dumps(cfg))
    mix = json.loads((base / "traffic" / "dpf.live.json").read_text())
    mix["name"] = "dpf.live_deep"
    (base / "traffic" / "dpf.live_deep.json").write_text(json.dumps(mix))
    name = "paper_vi_deep.dpf.live_deep"
    (base / "limits" / f"{name}.json").write_text(json.dumps(
        {"limits": {"mismatch_rounds_pct": 1.0}}))
    bench["configs"].append({"name": "paper_vi_deep", "source": "x",
                             "file": "perfbench/configs/paper_vi_deep.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": name, "config": "paper_vi_deep",
                               "traffic": "dpf.live_deep", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "any_cell_ms", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "x", "moves": "rounds_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.cell(name, spec.benchmark(tmp_path), base=base)
    assert cell["config"]["deployment"]["p_ten_blocks"] == 0.75
    assert cell["traffic"]["scheduler"] == "dpf"
    assert cell["limits"] == {"mismatch_rounds_pct": 1.0}
    # metrics without a ``workloads`` key reach the new cell too; those
    # that list their cells do not
    assert {m["name"] for m in cell["per_layer"]} == \
        {m["name"] for m in bench["per_layer"] if "workloads" not in m} \
        == {"any_cell_ms"}


def test_unknown_names_are_errors():
    bench = spec.benchmark(ROOT)
    with pytest.raises(KeyError):
        spec.cell("no_such.cell", bench)
    with pytest.raises(KeyError):
        spec.peaks("TPU v0 imaginary")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_limits_name_the_numbers_compared():
    from perfbench.harness import check
    nums = {"a": 0.5, "b": 2.0}
    ok, checks = check.verdict(nums, {"a": 1.0})
    assert ok and checks["b"]["limit"] is None
    assert not check.verdict(nums, {"a": 1.0, "b": 1.0})[0]
    assert not check.verdict(nums, {"c": 1.0})[0]       # limit, no number
    assert not check.verdict(nums, {})[0]


def test_entries_are_found_by_name():
    from perfbench.harness import runner
    assert callable(runner.entry_module("live").run)
    with pytest.raises(SystemExit, match="unknown entry"):
        runner.entry_module("no_such_entry")


def test_entry_without_its_check_is_refused(monkeypatch):
    """An entry that runs but brings no ``numbers`` (or no control, or no
    reference) is an error before any run, never a silent pass."""
    from perfbench.harness import runner
    bare = types.ModuleType("perfbench.harness.bare_entry")
    bare.run = lambda *a, **kw: {}
    monkeypatch.setitem(sys.modules, bare.__name__, bare)
    with pytest.raises(SystemExit, match="lacks numbers, control_raw, "
                                         "REFERENCE"):
        runner.entry_module("bare_entry")
    bare.numbers = bare.control_raw = lambda *a, **kw: {}
    with pytest.raises(SystemExit, match="lacks REFERENCE$"):
        runner.entry_module("bare_entry")


def test_every_entry_names_a_plain_reference():
    """Each cell's entry names its reference module, and that module
    imports nothing of the program."""
    from perfbench.harness import runner
    entries = {spec.cell(w["name"], spec.benchmark(ROOT))["traffic"]
               ["entry"] for w in spec.benchmark(ROOT)["workloads"]}
    assert entries == set(NUMBERS)
    for name in entries:
        ref = runner.entry_module(name).REFERENCE
        tree = ast.parse((ROOT / "perfbench" / "harness" /
                          f"{ref}.py").read_text())
        imported = {n.module or "" for n in ast.walk(tree)
                    if isinstance(n, ast.ImportFrom)}
        imported |= {a.name for n in ast.walk(tree)
                     if isinstance(n, ast.Import) for a in n.names}
        assert not any(m.split(".")[0] == "repro" for m in imported), ref
