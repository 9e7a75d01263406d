"""Every cell of BENCHMARK.json resolves its files by name, and the
files agree with the entries that name them."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import cells  # noqa: E402

BENCH = cells.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = cells.resolve(name)
    assert cell.chips == 1
    assert cell.config["reference"] and cells.reference_module(cell.config)
    assert float(cell.limits["max_gap"]["limit"]) > 0
    e2e = {m.name for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    moves = {m["name"]: m["moves"] for m in BENCH["per_layer"]}
    layers = {m["name"]: m["layer"] for m in BENCH["per_layer"]}
    for m in cell.per_layer:
        reader = cells.layer_reader(m.name)
        # the reader states the layer and the metric it moves, as declared
        assert reader.LAYER == layers[m.name]
        assert reader.MOVES == moves[m.name]
        # and the cell reports that end-to-end metric
        assert reader.MOVES in e2e
        assert callable(reader.read)


def test_every_file_is_named_by_an_entry():
    mixes = {w["traffic"] for w in BENCH["workloads"]}
    assert {p.stem for p in (HERE / "traffic").glob("*.json")} == mixes
    assert {p.stem for p in (HERE / "limits").glob("*.json")} == set(CELLS)
    assert {p.stem for p in (HERE / "layer_metrics").glob("*.py")} == {
        m["name"] for m in BENCH["per_layer"]}
    files = {c["file"] for c in BENCH["configs"]}
    assert {str(p.relative_to(cells.ROOT)) for p in (HERE / "configs").glob("*.json")} == files


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_names_a_program(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    config = json.loads((cells.ROOT / entry["file"]).read_text())
    program = cells.program_module(config)
    for attr in ("program_config", "leaf_init", "tiny"):
        assert callable(getattr(program, attr))
    assert isinstance(getattr(program, "RUN_CONFIG", {}), dict)


def test_every_program_is_named_by_a_config_or_is_the_default():
    named = {json.loads((cells.ROOT / c["file"]).read_text()).get(
        "program", cells.DEFAULT_PROGRAM) for c in BENCH["configs"]}
    assert {p.stem for p in (HERE / "programs").glob("*.py")} == named | {
        cells.DEFAULT_PROGRAM}


def test_config_files_state_their_cuts():
    for c in BENCH["configs"]:
        config = json.loads((cells.ROOT / c["file"]).read_text())
        assert config["source"] == c["source"]
        assert sorted(config["changed_from_source"]) == sorted(c["reduced"])


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        cells.resolve("no-such.cell")
