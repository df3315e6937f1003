"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

A cell names a configuration (``configs/<name>.json`` through the
configuration's ``file``), a traffic mix (``traffic/<name>.json``), and
the end-to-end and per-layer metrics that list it.  A metric's reader is
``metrics/<name>.py``, or ``metrics/<name up to its first dot>.py`` where
one reader serves several metrics (``decode_tick_ms.batch`` and a later
mix's ``decode_tick_ms.<mix>``).  Adding a cell, a mix or a metric adds files
and entries; nothing here names one.

Nor does anything here, in ``serve.py``, ``flops.py`` or ``run.py``, name a
model architecture.  A configuration's ``reference`` key names its
architecture, and a new one adds:

* a configuration file with the published ``model_type`` and the
  ``reference``;
* ``models/<reference>.py``: the plain reference and its weights
  (``make_weights``, ``logits_at``);
* ``counts/<reference>.py``: the operations and bytes a step needs
  (``decode_tick``, ``decode_token_flops``, ``prefill_flops``);
* ``programs/<reference>.py``: ``spec_from_config``, the program's spec
  of the architecture read from the configuration's keys, with a branch
  for each ``model_type`` of it that the program builds;
* ``configs/tiny-<reference>.json``: a toy of the architecture for the CPU
  rehearsal (``configs/tiny.json`` is the one of ``dense_gqa``).

A part that is missing is an error naming it, never another
architecture's.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str) -> Callable:
    """The ``read(run)`` function of a metric, found by its name."""
    for stem in (name, name.split(".", 1)[0]):
        path = HERE / "metrics" / f"{stem}.py"
        if path.is_file():
            return load_module(path, f"perfbench_metric_{stem}").read
    raise FileNotFoundError(f"no reader metrics/{name}.py for metric "
                            f"{name!r}")


@functools.lru_cache(maxsize=None)
def architecture(part: str, reference: str):
    """The module ``<part>/<reference>.py`` of one architecture: ``models``,
    ``counts`` or ``programs``."""
    path = HERE / part / f"{reference}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {part}/{reference}.py for the "
                                f"architecture {reference!r}")
    return load_module(path, f"perfbench_{part}_{reference}")


def rehearsal_config(reference: str, where: Path = HERE / "configs") -> dict:
    """The one tiny configuration (``tiny.json`` or ``tiny-*.json``) whose
    ``reference`` is ``reference``."""
    paths = [*where.glob("tiny.json"), *sorted(where.glob("tiny-*.json"))]
    found = [c for c in map(load_json, paths) if c["reference"] == reference]
    if len(found) != 1:
        raise LookupError(f"{len(found)} tiny configurations of the "
                          f"architecture {reference!r} in {where}; the "
                          f"rehearsal needs exactly one")
    return found[0]


def generator(kind: str):
    """The traffic generator module of a mix's ``kind``."""
    return load_module(HERE / "gen" / f"{kind}.py", f"perfbench_gen_{kind}")


def traffic_path(name: str) -> Path:
    return HERE / "traffic" / f"{name}.json"


def _metrics(entries: List[dict], cell: str) -> List[Metric]:
    out = []
    for m in entries:
        ws = m.get("workloads")
        if ws is not None and cell not in ws:
            continue
        out.append(Metric(m["name"], m["unit"], reader(m["name"])))
    return out


def load_cell(name: str, rehearse: bool = False,
              bench_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``.  ``rehearse`` keeps its
    metrics and traffic kind but swaps in the tiny configuration of its
    architecture and the tiny mix of that kind, for a run on the CPU."""
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_path.name}; "
                       f"cells: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic_name = w["traffic"]
    traffic = load_json(traffic_path(traffic_name))
    if rehearse:
        config = rehearsal_config(config["reference"])
        traffic_name = f"rehearsal-{traffic['kind']}"
        traffic = load_json(traffic_path(traffic_name))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic_name=traffic_name, traffic=traffic,
                end_to_end=_metrics(bench["end_to_end"], name),
                per_layer=_metrics(bench["per_layer"], name))


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]
