"""Every cell, configuration, traffic mix, limit and metric that
BENCHMARK.json names exists and loads, and names only what exists."""

import json

import pytest

from benchmarks.chip import peaks, spec, system

BENCH = spec.load()
RAW = BENCH.raw


@pytest.mark.parametrize("name", BENCH.cell_names())
def test_cell_loads(name):
    cell = spec.load().cell(name)
    assert cell.chips in (1, 4)
    assert (spec.HERE / "drivers" / f"{cell.traffic['driver']}.py").is_file()
    assert cell.limits and all("limit" in v for v in cell.limits.values())
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in names, f"{m['name']} moves a metric {name} does not report"


@pytest.mark.parametrize("entry", RAW["configs"], ids=lambda c: c["name"])
def test_config_matches_the_program(entry):
    config = json.loads((spec.ROOT / entry["file"]).read_text())
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == sorted(config["overrides"])
    assert config["source"] == entry["source"]
    system.arch_config(config)  # raises where the program differs from the stated sizes


@pytest.mark.parametrize("metric", RAW["per_layer"], ids=lambda m: m["name"])
def test_metric_reader_exists(metric):
    read = spec.load_metric_reader(metric["name"])
    assert read({}) is None  # nothing to read: no number, never a 0
    ends = {m["name"] for m in RAW["end_to_end"]}
    assert metric["moves"] in ends
    for w in metric.get("workloads", []):
        assert w in BENCH.cell_names()


def test_every_file_is_named():
    cells = {w["traffic"] for w in RAW["workloads"]}
    assert {p.stem for p in (spec.HERE / "traffic").glob("*.json")} == cells
    assert {p.stem for p in (spec.HERE / "limits").glob("*.json")} == set(BENCH.cell_names())
    assert {p.name[:-3] for p in (spec.HERE / "metrics").glob("*.py")} == {
        m["name"] for m in RAW["per_layer"]}
    assert {(spec.ROOT / c["file"]).resolve() for c in RAW["configs"]} == set(
        (spec.HERE / "configs").glob("*.json"))
    stated = {json.loads((spec.ROOT / c["file"]).read_text())["model"]["family"]
              for c in RAW["configs"]}
    assert stated <= {p.stem for p in (spec.HERE / "families").glob("*.py")} <= stated | {"dense"}
    kernels = "\n".join(p.read_text() for p in (spec.ROOT / "src/repro/kernels").glob("*.py"))
    for kind in (spec.HERE / "launches").glob("*.py"):
        assert f'name="{spec.load_launch(kind.stem).NAME}"' in kernels, kind.name


@pytest.mark.parametrize("path", sorted((spec.HERE / "families").glob("*.py")), ids=lambda p: p.stem)
def test_family_gives_the_contract(path):
    """Each family gives its reference, control, step FLOPs and stated
    keys, and, like ``reference.py``, imports nothing of the program."""
    fam = spec.load_family(path.stem)
    for name in ("served_gaps", "control_gaps", "prefill_flops", "decode_flops"):
        assert callable(getattr(fam, name)), name
    assert all(isinstance(f, str) for f in fam.FIELDS.values())
    assert "repro" not in path.read_text()


def test_unknown_device_is_an_error():
    assert peaks.peak_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak_for("TPU v9 imaginary")
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak_for("cpu")
