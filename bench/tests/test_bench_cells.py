"""Every cell of BENCHMARK.json resolves to its configuration, traffic,
limits and metric files, and the file keeps the benchmark's format."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, reference  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = harness.load_cell(cell)
    assert c.chips in (1, 4)
    assert c.chips == c.config["chips"]
    from repro.api import SVDSpec
    spec = SVDSpec(**c.traffic["spec"])
    assert spec.method in ("fsvd", "fsvd_sharded")
    assert set(c.traffic) <= {"spec", "entry"}
    assert c.entry in harness.ENTRIES
    assert (spec.method == "fsvd_sharded") == (c.config["layout"] == "rows")
    assert set(c.limits) == set(reference.NUMBERS[c.entry])
    for k in reference.NUMBERS[c.entry]:
        if c.limits[k].get("exact"):
            assert c.limits[k]["limit"] == 0
        else:
            assert 0 < c.limits[k]["limit"] < 1
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(harness.load_reader(m["name"]))


def test_format():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + [
        c["name"] for c in SPEC["configs"]]
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"] for m in SPEC["end_to_end"]} >= {"setup_s"}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        reader = harness.quantity(m["name"])
        assert (ROOT / "bench" / "metrics" / f"{reader}.py").is_file()
    for w in SPEC["workloads"]:
        assert 1 <= len(w["why"]) <= 200
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}


def test_traffic_with_a_key_the_harness_does_not_run_is_refused(tmp_path):
    """A traffic file may hold only ``spec``: a key such as ``clients``
    would promise a load that the closed loop of one client never makes."""
    cell = CELLS[0]
    w = {x["name"]: x for x in SPEC["workloads"]}[cell]
    for kind, name in (("configs", w["config"]), ("limits", cell)):
        (tmp_path / "bench" / kind).mkdir(parents=True)
        (tmp_path / "bench" / kind / f"{name}.json").write_text(
            (ROOT / "bench" / kind / f"{name}.json").read_text())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(SPEC))
    traffic = tmp_path / "bench" / "traffic"
    traffic.mkdir()
    body = json.loads(
        (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    (traffic / f"{w['traffic']}.json").write_text(json.dumps(body))
    assert harness.load_cell(cell, tmp_path).traffic == body
    (traffic / f"{w['traffic']}.json").write_text(
        json.dumps({**body, "clients": 4}))
    with pytest.raises(ValueError, match="clients"):
        harness.load_cell(cell, tmp_path)


def test_unknown_entry_is_refused(tmp_path):
    cell = CELLS[0]
    w = {x["name"]: x for x in SPEC["workloads"]}[cell]
    for kind, name in (("configs", w["config"]), ("limits", cell)):
        (tmp_path / "bench" / kind).mkdir(parents=True)
        (tmp_path / "bench" / kind / f"{name}.json").write_text(
            (ROOT / "bench" / kind / f"{name}.json").read_text())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(SPEC))
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "traffic" / f"{w['traffic']}.json").write_text(
        json.dumps({"entry": "factorize_many", "spec": {"rank": 5}}))
    with pytest.raises(ValueError, match="entry"):
        harness.load_cell(cell, tmp_path)


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no_such.cell")
