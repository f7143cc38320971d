"""Find a cell's pieces by name: ``BENCHMARK.json`` names the cell, its
configuration and its traffic mix; each lives in a file of its own.

- configuration ``<c>``: the file the manifest's ``configs`` entry names
  (``bench/configs/<c>.json``), with its plain reference
  ``bench/reference/<family>.py`` beside it;
- traffic mix ``<t>``: ``bench/traffic/<t>.json``, read by
  ``bench/generator.py``;
- metric ``<m>``: ``bench/metrics/<m>.py``, whose ``read(run)`` returns a
  number or None; where that file is missing, the reader of the quantity
  the name splits by cell kind (``engine.tick_ms.chat`` →
  ``bench/metrics/engine.tick_ms.py``).

Adding a cell means adding files and one ``workloads`` entry; no file
here changes.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Spec:
    """Read-only attribute view of a JSON object; hashable, so a jitted
    function can take it as a static argument."""

    def __init__(self, d: dict):
        self._d = d
        self._key = json.dumps(d, sort_keys=True)

    def __getattr__(self, k):
        try:
            v = self.__dict__["_d"][k]
        except KeyError:
            raise AttributeError(k) from None
        return Spec(v) if isinstance(v, dict) else v

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, Spec) and self._key == other._key


@dataclass
class Cell:
    name: str
    chips: int
    config: dict                # the configuration file, as run
    traffic_name: str
    traffic: dict               # the traffic mix's data file
    end_to_end: List[dict]      # metrics this cell reports with --trace 0
    per_layer: List[dict]       # metrics this cell reports with --trace 1

    @property
    def model(self) -> Spec:
        return Spec(self.config["model"])

    def reference(self):
        return importlib.import_module(
            f"bench.reference.{self.config['reference']}")


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str, e2e_names: Optional[set] = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def find_cell(name: str, root: Path = ROOT,
              manifest: Optional[dict] = None) -> Cell:
    man = manifest if manifest is not None else load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in man["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in man["end_to_end"] if _reports(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in man["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(w["chips"]), config, w["traffic"], traffic, e2e,
                per_layer)


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """``read(run)`` of ``bench/metrics/<name>.py``, or of the file named
    without the last dotted part."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = path.with_name(f"{name.rsplit('.', 1)[0]}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: List[dict], run, root: Path = ROOT) -> Dict[str, dict]:
    """Each metric whose reader finds something in ``run``."""
    out = {}
    for m in metrics:
        v = metric_reader(m["name"], root)(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
