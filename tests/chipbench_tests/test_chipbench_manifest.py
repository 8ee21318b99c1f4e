"""BENCHMARK.json against the rules the driver holds it to, and against the
files it names."""

import json
import os
import re

import pytest

from chipbench import manifest

MAN = manifest.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = MAN["end_to_end"] + MAN["per_layer"]
ALL_NAMES = ([m["name"] for m in METRICS]
             + [w[k] for w in MAN["workloads"]
                for k in ("name", "config", "traffic")]
             + [c["name"] for c in MAN["configs"]]
             + [k for c in MAN["configs"] for k in c["reduced"]])


def test_keys_are_exactly_the_contracts():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(MAN)) < 64 * 1024
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51


@pytest.mark.parametrize("name", sorted(set(ALL_NAMES)))
def test_name_is_letters_digits_and_punctuation_allowed(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    assert UNIT.match(m["unit"]), m["unit"]
    assert m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES
    cells = {w["name"] for w in MAN["workloads"]}
    assert set(m.get("workloads", [])) <= cells
    if m in MAN["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    else:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in {e["name"] for e in MAN["end_to_end"]}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        assert os.path.isfile(os.path.join(
            manifest.HERE, "metrics", m["name"] + ".py")), \
            "every per-layer metric is a reader file of its own"


def test_no_two_share_a_name():
    for group in (METRICS, MAN["workloads"], MAN["configs"]):
        names = [g["name"] for g in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_setup_s_is_an_end_to_end_metric_of_every_cell():
    setup = [m for m in MAN["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.1


@pytest.mark.parametrize("cell", MAN["workloads"], ids=lambda w: w["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\t" not in cell["why"]
    c = manifest.Cell(MAN, cell["name"])
    assert c.mix["kind"] and os.path.isfile(os.path.join(
        manifest.HERE, "kinds", c.mix["kind"] + ".py"))
    for sub, key in (("builders", "builder"), ("reference", "reference")):
        assert os.path.isfile(os.path.join(manifest.HERE, sub,
                                           c.cfg[key] + ".py"))
    e2e = {m["name"] for m in c.end_to_end()}
    assert "setup_s" in e2e and len(e2e) >= 2, "setup_s and one more"
    assert c.per_layer(), "at least one per-layer metric"


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = sum(1 for w in MAN["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MAN["workloads"]) // 4)


@pytest.mark.parametrize("conf", MAN["configs"], ids=lambda c: c["name"])
def test_config_entry(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert any(conf["file"].startswith(p + "/") for p in MAN["paths"])
    assert 1 <= len(conf["source"]) <= 200
    assert conf["name"] in {w["config"] for w in MAN["workloads"]}
    cfg = manifest.load_json(os.path.join(manifest.ROOT, conf["file"]))
    assert cfg["name"] == conf["name"] and cfg["limits"]
    widths = re.compile(r"(hidden|intermediate|latent|state|_dim$|_rank$|"
                        r"head|expansion|per_tok)")
    assert not [k for k in conf["reduced"] if widths.search(k)]


def test_command_and_paths():
    assert MAN["command"] == ["python3", "-m", "chipbench.run"]
    for p in MAN["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) and ".." not in p
        assert os.path.isdir(os.path.join(manifest.ROOT, p))


def test_files_under_paths_are_named_from_names_characters():
    for p in MAN["paths"]:
        for dirpath, dirs, files in os.walk(os.path.join(manifest.ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


def test_traffic_mixes_are_data_files():
    for w in MAN["workloads"]:
        assert os.path.isfile(os.path.join(manifest.HERE, "traffic",
                                           w["traffic"] + ".json"))


def test_a_share_has_the_unit_percent_and_mfu_stands_beside_rooflines():
    per = MAN["per_layer"]
    for m in per:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in per:
        if m["name"].endswith("_roofline"):
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o.get(
                           "workloads", m["workloads"])) for o in per)
