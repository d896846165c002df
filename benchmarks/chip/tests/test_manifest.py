"""The loader finds every piece of a cell by name, from data alone."""
import json
import os

import pytest
from conftest import CHIP, DATA

import harness
import manifest

ROOT = os.path.dirname(os.path.dirname(CHIP))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_each_cell_finds_config_mix_and_readers(name):
    cell = manifest.load_cell(name, ROOT)
    assert cell.config["max_len"] > 0 and cell.mix["loop"] in ("open",
                                                              "closed")
    names = {m["name"] for m in cell.end_to_end}
    # set-up time and at least one other end-to-end metric
    assert "setup_s" in names and len(names) >= 2
    for m in cell.end_to_end + cell.per_layer:
        assert callable(manifest.reader(m["name"]))
        if m in cell.per_layer:
            assert m["moves"] in names   # the cell reports what it moves


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(CHIP, "configs"))))
def test_program_config_is_the_published_one(name):
    """Every configuration file, declared in a cell or not yet."""
    with open(os.path.join(CHIP, "configs", f"{name}.json")) as f:
        config = json.load(f)
    cell = manifest.Cell(name, {}, config, {}, [], [])
    cfg = harness.program_config(cell)
    assert cfg.dtype == "float32"


def test_a_data_only_cell_is_picked_up():
    """A cell that is nothing but new data files (tests/data: manifest,
    cell, config and mix) loads through the same loader."""
    cell = manifest.load_cell("tiny.overcommit", DATA, DATA)
    assert cell.config["published"]["hidden_size"] == 64
    assert cell.mix["sessions"]["pick"] == "zipf"
    assert {m["name"] for m in cell.per_layer} >= {"swap_pages_s"}


def test_cell_file_must_agree_with_the_manifest(tmp_path):
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"][0]["chips"] = 4
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(ValueError, match="chips"):
        manifest.load_cell(bench["workloads"][0]["name"], str(tmp_path))


def test_unknown_device_kind_is_an_error():
    assert manifest.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        manifest.peaks("TPU v9 imaginary")


def test_manifest_has_the_required_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in BENCH["workloads"]:
        assert w["config"] in {c["name"] for c in BENCH["configs"]}
    layer_names = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in n for n in layer_names)
