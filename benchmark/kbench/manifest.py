"""Resolve a cell of BENCHMARK.json to its files, by name.

A cell `<config>.<traffic>` is three data files the harness finds by name:
`configs/<config>.json`, `traffic/<traffic>.json` and
`cells/<config>.<traffic>.json` (what belongs to the pair and to neither
half: an open loop's rate, a closed loop's client count).  A per-layer
metric `<name>` is `layer_metrics/<name>.py`.  Adding any of them is adding
files and BENCHMARK.json entries; nothing here lists names.
"""

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class ManifestError(Exception):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"missing file: {os.path.relpath(path, ROOT)}") from None
    except json.JSONDecodeError as e:
        raise ManifestError(f"{os.path.relpath(path, ROOT)}: {e}") from None


def load_manifest(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


#: keys of a configuration's file that are the benchmark's own, not the model's
OWN_KEYS = ("deployment", "assumed", "source", "reduced", "rehearsal")


@dataclass
class Cell:
    """One entry of `workloads`, with its three data files loaded."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict  # configs/<config>.json: the HF config plus a `deployment` group
    traffic: dict  # traffic/<traffic>.json
    pair: dict  # cells/<name>.json
    end_to_end: list  # manifest entries reported by this cell
    per_layer: list

    @property
    def hf_config(self) -> dict:
        """The model's config.json as run: every key but the benchmark's own."""
        return {k: v for k, v in self.config.items() if k not in OWN_KEYS}

    @property
    def deployment(self) -> dict:
        return self.config["deployment"]


def _in_cell(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve_cell(name: str, root: str = ROOT, bench_dir: str = BENCH_DIR) -> Cell:
    manifest = load_manifest(root)
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json (has: {known})")
    cfg_entry = next(
        (c for c in manifest["configs"] if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise ManifestError(f"workload {name!r} names unknown config {entry['config']!r}")
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config_name=entry["config"],
        traffic_name=entry["traffic"],
        config=_load_json(os.path.join(root, cfg_entry["file"])),
        traffic=_load_json(
            os.path.join(bench_dir, "traffic", entry["traffic"] + ".json")),
        pair=_load_json(os.path.join(bench_dir, "cells", name + ".json")),
        end_to_end=[m for m in manifest["end_to_end"] if _in_cell(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _in_cell(m, name)],
    )


def load_reader(metric_name: str, bench_dir: str = BENCH_DIR):
    """The module `layer_metrics/<name>.py`; it has `read(run)`."""
    path = os.path.join(bench_dir, "layer_metrics", metric_name + ".py")
    if not os.path.exists(path):
        raise ManifestError(
            f"per-layer metric {metric_name!r} has no reader "
            f"{os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric_name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    if not callable(getattr(module, "read", None)):
        raise ManifestError(f"{os.path.relpath(path, ROOT)} defines no read(run)")
    return module


def load_peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    """The published peaks of one chip; an unknown device is an error."""
    table = _load_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table["devices"]:
        raise ManifestError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(has: {', '.join(table['devices'])}); add its published peaks "
            "with their source — there is no default")
    return table["devices"][device_kind]
