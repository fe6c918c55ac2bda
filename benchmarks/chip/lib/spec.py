"""Finds a cell's files by the names in BENCHMARK.json and refuses what it
does not know.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric is a file of its own under benchmarks/chip/:

    configs/<config>.json + configs/<config>/config.json
    traffic/<mix>.json
    cells/<cell>.json
    layer_metrics/<metric>.json  ->  readers/<reader>.py
    devices.json                 (peaks and trace plane names by device_kind)

A later PR adds files and BENCHMARK.json entries; nothing here names a cell.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP_DIR))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class SpecError(Exception):
    """The benchmark's data files do not describe a runnable cell."""


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise SpecError(f"cannot read {path}: {e}") from e
    except ValueError as e:
        raise SpecError(f"{path} is not JSON: {e}") from e


def _named_file(kind: str, name: str) -> dict:
    if not NAME_RE.match(name):
        raise SpecError(f"{kind} name {name!r} is not a name")
    path = os.path.join(CHIP_DIR, kind, name + ".json")
    if not os.path.exists(path):
        raise SpecError(f"unknown {kind} entry {name!r}: no {path}")
    return load_json(path)


def load_device(device_kind: str) -> dict:
    """The peaks and trace plane names of one device kind. A kind that is
    not in the table is an error, never a default."""
    table = load_json(os.path.join(CHIP_DIR, "devices.json"))
    if device_kind not in table["devices"]:
        raise SpecError(
            f"device_kind {device_kind!r} is not in benchmarks/chip/"
            f"devices.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]


def load_reader(name: str):
    """readers/<name>.py, which must define read(ctx, args)."""
    if not NAME_RE.match(name):
        raise SpecError(f"reader name {name!r} is not a name")
    path = os.path.join(CHIP_DIR, "readers", name + ".py")
    if not os.path.exists(path):
        raise SpecError(f"unknown reader {name!r}: no {path}")
    spec = importlib.util.spec_from_file_location(
        f"chip_reader_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"reader {name!r} defines no read(ctx, args)")
    return mod


@dataclasses.dataclass
class LayerMetric:
    name: str
    unit: str
    reader: object   # the reader module
    args: dict


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    model_dir: str        # configs/<config>/ (holds the model's config.json)
    traffic: dict         # traffic/<mix>.json
    load: dict            # the cell's rate or client count
    end_to_end: dict      # name -> unit, the metrics a --trace 0 run owes
    per_layer: list       # [LayerMetric], what a --trace 1 run owes

    def owed(self, trace: bool) -> dict:
        """name -> unit of every metric this kind of run must print."""
        if trace:
            return {m.name: m.unit for m in self.per_layer}
        return dict(self.end_to_end)


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark_path: str | None = None) -> Cell:
    bench = load_json(benchmark_path or os.path.join(REPO, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(
            f"unknown workload {name!r}: BENCHMARK.json has "
            f"{[w['name'] for w in bench['workloads']]}")
    cell = _named_file("cells", name)
    for key in ("config", "traffic"):
        if cell.get(key) != entry[key]:
            raise SpecError(
                f"cells/{name}.json says {key} {cell.get(key)!r}, "
                f"BENCHMARK.json says {entry[key]!r}")
    if not any(c["name"] == entry["config"] for c in bench["configs"]):
        raise SpecError(f"BENCHMARK.json lists no config {entry['config']!r}")
    config = _named_file("configs", entry["config"])
    model_dir = os.path.join(CHIP_DIR, "configs", entry["config"])
    if not os.path.exists(os.path.join(model_dir, "config.json")):
        raise SpecError(f"no {model_dir}/config.json for the worker to load")
    if config.get("chips") != entry["chips"]:
        raise SpecError(
            f"configs/{entry['config']}.json is laid out for "
            f"{config.get('chips')} chip(s), the cell asks for "
            f"{entry['chips']}")
    traffic = _named_file("traffic", entry["traffic"])

    e2e_names = {m["name"] for m in bench["end_to_end"]}
    end_to_end = {m["name"]: m["unit"] for m in bench["end_to_end"]
                  if _in_cell(m, name)}
    per_layer = []
    for m in bench["per_layer"]:
        if not _in_cell(m, name):
            continue
        if m["moves"] not in end_to_end:
            raise SpecError(
                f"per-layer metric {m['name']!r} moves {m['moves']!r}, "
                f"which cell {name!r} does not report "
                f"({'unknown metric' if m['moves'] not in e2e_names else 'other cells do'})")
        mfile = _named_file("layer_metrics", m["name"])
        for key in ("unit", "layer", "moves"):
            if mfile.get(key) != m[key]:
                raise SpecError(
                    f"layer_metrics/{m['name']}.json says {key} "
                    f"{mfile.get(key)!r}, BENCHMARK.json says {m[key]!r}")
        per_layer.append(LayerMetric(
            m["name"], m["unit"], load_reader(mfile["reader"]),
            mfile.get("args", {})))
    for metric_name, unit in list(end_to_end.items()) + [
            (m.name, m.unit) for m in per_layer]:
        if not NAME_RE.match(metric_name) or not UNIT_RE.match(unit):
            raise SpecError(f"metric {metric_name!r} [{unit!r}]: bad name "
                            f"or unit")
    if "setup_s" not in end_to_end or len(end_to_end) < 2 or not per_layer:
        raise SpecError(
            f"cell {name!r} must report setup_s, one more end-to-end "
            f"metric and a per-layer metric")
    return Cell(name, entry["chips"], config, model_dir, traffic,
                cell["load"], end_to_end, per_layer)
