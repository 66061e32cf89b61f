"""Finds a cell's configuration, workload, model family, traffic kind and
metric readers by the names `BENCHMARK.json` and the files give them."""
from __future__ import annotations

import importlib.util
import json
import pathlib
from types import ModuleType
from typing import List

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = pathlib.Path(__file__).resolve().parent
#: where `family` finds `<family>.py`; a test may point it elsewhere
FAMILIES = HERE / "families"


def manifest() -> dict:
  with open(ROOT / "BENCHMARK.json") as f:
    return json.load(f)


def _by_name(entries: List[dict], name: str, what: str) -> dict:
  for e in entries:
    if e["name"] == name:
      return e
  raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_json(rel: str) -> dict:
  with open(ROOT / rel) as f:
    return json.load(f)


def cell(name: str, man: dict | None = None) -> dict:
  """The workload entry, with its configuration file and workload file."""
  man = man or manifest()
  w = _by_name(man["workloads"], name, "workload")
  c = _by_name(man["configs"], w["config"], "configuration")
  return dict(entry=w, config=load_json(c["file"]),
              workload=load_json(f"bench/workloads/{name}.json"))


def metrics_for(cell_name: str, trace: bool, man: dict | None = None
                ) -> List[dict]:
  """The cell's end-to-end metrics (trace off) or per-layer ones (trace on):
  entries whose `workloads` list names the cell, or that have none."""
  man = man or manifest()
  group = man["per_layer" if trace else "end_to_end"]
  return [m for m in group
          if "workloads" not in m or cell_name in m["workloads"]]


def _module(path: pathlib.Path) -> ModuleType:
  spec = importlib.util.spec_from_file_location(
      "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


def family(name: str) -> ModuleType:
  """The model family a configuration file names (`"family"`): weights,
  reference block and step FLOPs (`families/dense.py` lists them)."""
  return _module(FAMILIES / f"{name}.py")


def traffic_kind(kind: str) -> ModuleType:
  return _module(HERE / "traffic" / f"{kind}.py")


def metric_reader(name: str) -> ModuleType:
  return _module(HERE / "metrics" / f"{name}.py")
