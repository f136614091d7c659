"""``BENCHMARK.json`` and the files it names, found by name.

Everything that belongs to one configuration, one traffic mix or one
metric sits in a file of its own under ``perfbench/``:

* ``configs/<config>.json``: a deployment (the ``file`` of its
  ``configs`` entry).  Beside its sizes it names two files by key:
  ``overlay.family`` its overlay generator and ``reference`` its plain
  reference;
* ``overlays/<family>.py``: a generator with ``build(peers,
  topology_seed, **family_args)`` that returns a
  ``harness.overlay.Overlay``;
* ``references/<reference>.py``: the plain reference of the answers,
  with ``params(config)``, which refuses a configuration key it does
  not know, and ``query(ov, origin, seed, config)``, which returns a
  ``harness.reference.Answer`` (see ``harness.check``);
* ``traffic/<traffic>.json``: a traffic mix (see ``harness.traffic``);
* ``metrics/<metric>.py``: a reader with ``read(run)`` that returns the
  metric's value from a finished run (``harness.cell.Run``), or None
  when the run holds nothing to read.  A metric split by the cells it
  reports in (``<metric>.<part>``, as ``engine_s_per_query.closed``)
  is read by the reader of ``<metric>``.

A new deployment, cell or metric is new files plus entries in
``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(HERE)


def load(root: str = CHECKOUT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(bench: dict, workload: str, root: str = CHECKOUT) -> dict:
    """The cell ``workload`` with its configuration and traffic loaded:
    ``{"cell", "config", "traffic", "end_to_end", "per_layer"}``, the
    last two being the metric entries this cell reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def mine(metrics: List[dict]) -> List[dict]:
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]
    return {"cell": w, "config": config, "traffic": traffic,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def module(kind: str, name: str, root: str = CHECKOUT):
    """The module ``perfbench/<kind>/<name>.py`` under ``root``; an
    unknown ``name`` is an error that lists the names there are."""
    folder = os.path.join(root, os.path.basename(HERE), kind)
    path = os.path.join(folder, name + ".py")
    if not os.path.isfile(path):
        known = sorted(f[:-3] for f in os.listdir(folder)
                       if f.endswith(".py")) if os.path.isdir(folder) else []
        raise ValueError(f"no {kind} file {name!r} under {folder}; "
                         f"known: {known}")
    key = re.sub(r"\W", "_", f"perfbench_{kind}_{name}")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    # a dataclass under postponed annotations looks its module up here
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def reader(name: str) -> Callable:
    """``read`` of ``metrics/<base>.py``, ``base`` being ``name`` up to
    its first dot."""
    return module("metrics", name.split(".", 1)[0]).read


def read_metrics(entries: List[dict], run) -> Dict[str, dict]:
    """Each metric's reading with its unit; a metric whose reader finds
    nothing to read is left out."""
    out = {}
    for m in entries:
        v = reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
