"""BENCHMARK.json and the files it names: every configuration, traffic
mix and metric is found by its name, so a later PR adds a cell by adding
files and entries, never by editing these."""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


@dataclass
class Cell:
    name: str
    entry: dict          # the workloads entry of BENCHMARK.json
    conf: dict           # bench/configs/<config>.json
    conf_dir: str
    traffic: dict        # bench/traffic/<traffic>.json
    end_to_end: list     # metric entries this cell reports, trace 0
    per_layer: list      # metric entries this cell reports, trace 1


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, manifest: dict, root: str = ROOT) -> Cell:
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")
    conf_entry = next(c for c in manifest["configs"]
                      if c["name"] == entry["config"])
    conf_path = os.path.join(root, conf_entry["file"])
    e2e = [m for m in manifest["end_to_end"] if _reports(m, name)]
    names = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return Cell(
        name=name, entry=entry, conf=_json(conf_path),
        conf_dir=os.path.dirname(conf_path),
        traffic=_json(os.path.join(BENCH, "traffic",
                                   entry["traffic"] + ".json")),
        end_to_end=e2e, per_layer=layer)


def metric_file(name: str) -> str:
    """The reader of metric ``name``: ``metrics/<name>.py``, else the
    reader of the name with its last ``.part`` cut off, so that one
    quantity split by the end-to-end metric it moves
    (``device_idle_share.deep``, ``.small``) has one reader."""
    while True:
        path = os.path.join(BENCH, "metrics", name + ".py")
        if os.path.exists(path) or "." not in name:
            return path
        name = name.rsplit(".", 1)[0]


def driver_file(name: str) -> str:
    return os.path.join(BENCH, "drivers", name + ".py")


def load_module(path: str):
    """Import a reader or driver from its file (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
