"""BENCHMARK.json against the rules the harness depends on: names and
units in the allowed characters, every cell's files present, every
per-layer metric's cells reporting the metric it moves, every
configuration used, every metric with a reader."""
import json
import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parents[2]
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def _metrics():
  return MAN["end_to_end"] + MAN["per_layer"]


def _reports(metric, cell):
  return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys_and_names():
  assert set(MAN) == KEYS
  names = [e["name"] for k in ("configs", "workloads") for e in MAN[k]]
  names += [m["name"] for m in _metrics()]
  assert len(names) == len(set(names))
  for n in names:
    assert NAME.match(n), n
  for w in MAN["workloads"]:
    assert NAME.match(w["config"]) and NAME.match(w["traffic"])
  for c in MAN["configs"]:
    assert all(NAME.match(k) for k in c["reduced"])
  for m in _metrics():
    assert UNIT.match(m["unit"]), m
    assert m["better"] in ("lower", "higher")


def test_every_file_is_under_paths_and_present():
  paths = MAN["paths"]
  for c in MAN["configs"]:
    assert any(c["file"].startswith(p + "/") for p in paths)
    assert (ROOT / c["file"]).is_file()
  for w in MAN["workloads"]:
    assert (ROOT / "bench" / "workloads" / f"{w['name']}.json").is_file()
  for m in _metrics():
    assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file(), m


def test_every_configuration_has_a_cell():
  used = {w["config"] for w in MAN["workloads"]}
  assert used == {c["name"] for c in MAN["configs"]}


def test_per_layer_cells_report_what_they_move():
  e2e = {m["name"]: m for m in MAN["end_to_end"]}
  for m in MAN["per_layer"]:
    assert m["moves"] in e2e, m
    for cell in m["workloads"]:
      assert _reports(e2e[m["moves"]], cell), (m["name"], cell)


def test_every_cell_reports_setup_another_metric_and_a_layer():
  for w in MAN["workloads"]:
    e2e = [m["name"] for m in MAN["end_to_end"] if _reports(m, w["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
    assert any(_reports(m, w["name"]) for m in MAN["per_layer"])


def test_bounds():
  for m in MAN["end_to_end"]:
    assert 0.01 <= m["bound"] <= 0.25, m
  assert {m["name"]: m["bound"] for m in MAN["end_to_end"]}["setup_s"] <= 0.25


def test_every_configuration_names_a_family_with_a_file():
  for c in MAN["configs"]:
    family = json.loads((ROOT / c["file"]).read_text())["family"]
    assert NAME.match(family), c["name"]
    assert (ROOT / "bench" / "families" / f"{family}.py").is_file(), family
