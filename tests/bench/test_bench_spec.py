"""BENCHMARK.json is well formed, and every cell resolves its
configuration, traffic mix and generator, driver, reference, control and
metric readers by name; every substrate, used by a cell or not, brings the
files `bench/harness.py` lists."""
import importlib
import importlib.util
import json
import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

import harness  # noqa: E402
import traffic_gen  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
SUBSTRATES = sorted(p.stem for p in (ROOT / "bench" / "substrates")
                    .glob("*.py") if not p.stem.startswith("_"))
DRIVER_METHODS = ("setup", "run_window", "check", "layer_context")


def hold_to_the_contract(substrate: str):
    """The files a substrate brings (`bench/harness.py`'s docstring)."""
    mod = importlib.import_module(f"substrates.{substrate}")
    for name in DRIVER_METHODS:
        assert callable(getattr(getattr(mod, "Driver", None), name, None)), \
            f"substrates.{substrate}.Driver has no {name}"
    assert callable(getattr(mod, "small", None)), \
        f"substrates.{substrate} defines no small(cell)"
    assert (ROOT / "bench" / "reference" / f"{substrate}.py").is_file()
    control = harness.load_module(
        ROOT / "tests" / "bench" / "controls" / f"{substrate}.py", "control")
    assert callable(control.in_place)


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    for word in SPEC["command"]:
        if word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in SPEC["paths"])
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_entry_keys():
    names = []
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        names.append(c["name"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert len(set(names)) == len(names)
    assert len({w["name"] for w in SPEC["workloads"]}) == len(CELLS)
    assert len({(w["config"], w["traffic"]) for w in SPEC["workloads"]}) \
        == len(CELLS)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", CELLS)) <= set(
            CELLS)


def test_configuration_files_state_what_is_reduced():
    for c in SPEC["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert (ROOT / "bench" / "substrates"
                / f"{conf['substrate']}.py").is_file()
        assert (ROOT / "bench" / "reference"
                / f"{conf['substrate']}.py").is_file()
        assert set(c["reduced"]) == set(conf["reduced"])
        assert conf["assumed"] and conf["limits"]


@pytest.mark.parametrize("substrate", SUBSTRATES)
def test_every_substrate_holds_the_contract(substrate):
    hold_to_the_contract(substrate)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_by_name(cell):
    c = harness.load_cell(cell)
    hold_to_the_contract(c["config_file"]["substrate"])
    assert c["chips"] in (1, 4)
    assert callable(traffic_gen.generator(c["traffic_file"]["generator"]))
    e2e = [m["name"] for m in c["end_to_end"]]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c["per_layer"], f"{cell} reports no per-layer metric"
    for m in c["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    assert importlib.util.find_spec(
        f"substrates.{c['config_file']['substrate']}") is not None
