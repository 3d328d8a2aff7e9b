"""Find everything one cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each metric has a name.
The files are looked up by those names, so a later change adds a cell,
a mix or a metric by adding files and entries, never by editing one:

    configs/<config>.json      sizes, serving settings, ``family``
    references/<family>.py     plain reference and weight maker
    traffic/<mix>.json         traffic parameters
    metrics/<metric>.py        ``read(ctx)`` -> number or None; a
                               metric split by the cells' end-to-end
                               metric (``tick_ms.open``) falls back to
                               the reader of its stem (``tick_ms.py``)
    checks/<workload>.json     limits of the correctness comparison
    peaks.json                 chip peaks, by ``device_kind``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parents[1]          # benchmarks/serve


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    reader: Optional[Callable] = None     # per-layer metrics only


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict
    family: object                # the reference module
    mix_name: str
    mix: Dict
    checks: Dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _metric_applies(entry: Dict, workload: str, e2e_names: List[str]) -> bool:
    if "workloads" in entry:
        return workload in entry["workloads"]
    return entry.get("moves") is None or entry["moves"] in e2e_names


def metric_file(root: Path, name: str) -> Path:
    """``metrics/<name>.py``, else the reader of the name's stem."""
    path = root / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = root / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    return path


def load_cell(bench: Dict, workload: str, root: Path = HERE) -> Cell:
    """The cell ``workload`` of the parsed ``BENCHMARK.json``, with its
    files read from ``root`` (the benchmark's directory)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = _read_json(root / "configs" / f"{w['config']}.json")
    family = _load_module(root / "references" / f"{conf['family']}.py",
                          f"servebench_ref_{conf['family']}")
    mix = _read_json(root / "traffic" / f"{w['traffic']}.json")
    checks_path = root / "checks" / f"{workload}.json"
    checks = _read_json(checks_path) if checks_path.exists() else {}
    e2e = [Metric(m["name"], m["unit"]) for m in bench["end_to_end"]
           if _metric_applies(m, workload, [])]
    e2e_names = [m.name for m in e2e]
    layer = []
    for m in bench["per_layer"]:
        if not _metric_applies(m, workload, e2e_names):
            continue
        mod = _load_module(metric_file(root, m["name"]),
                           "servebench_metric_" + m["name"].replace(".", "_"))
        layer.append(Metric(m["name"], m["unit"], mod.read))
    return Cell(workload, int(w["chips"]), w["config"], conf, family,
                w["traffic"], mix, checks, e2e, layer)


def peak_for(kind: str, root: Path = HERE) -> Dict:
    """The chip's peaks; a ``device_kind`` missing from the table is an
    error, never a default."""
    table = _read_json(root / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device_kind {kind!r} is not in peaks.json "
                       f"(known: {sorted(table['devices'])})")
    return table["devices"][kind]
