"""Finding the benchmark's pieces by the names ``BENCHMARK.json`` gives them.

A cell is ``workloads/<cell>.json``; it names its configuration, whose sizes
are ``configs/<config>.json``, whose program side is ``configs/<config>.py``
and whose plain reference is ``configs/<config>_reference.py``.  A per-layer
metric is ``metrics/<metric>.py``.  Adding a cell, a configuration or a
metric adds files and entries; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re
import sys
from types import ModuleType

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _checked(name: str) -> str:
    if not NAME_RE.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def _load_module(path: pathlib.Path, module_name: str) -> ModuleType:
    if module_name in sys.modules:
        return sys.modules[module_name]
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[module_name]
        raise
    return mod


def _mangle(kind: str, name: str) -> str:
    return f"chipbench_{kind}_" + re.sub(r"[^A-Za-z0-9_]", "_", name)


def benchmark() -> dict:
    """``BENCHMARK.json`` at the root of the checkout."""
    return json.loads((CHECKOUT / "BENCHMARK.json").read_text())


def workload(name: str) -> dict:
    wl = json.loads((HERE / "workloads" / f"{_checked(name)}.json").read_text())
    wl.setdefault("name", name)
    return wl


def config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{_checked(name)}.json").read_text())


def config_module(name: str) -> ModuleType:
    return _load_module(HERE / "configs" / f"{_checked(name)}.py", _mangle("config", name))


def load_reference(name: str) -> ModuleType:
    return _load_module(HERE / "configs" / f"{_checked(name)}_reference.py",
                        _mangle("reference", name))


def metric_module(name: str) -> ModuleType:
    mod = _load_module(HERE / "metrics" / f"{_checked(name)}.py", _mangle("metric", name))
    if getattr(mod, "NAME", None) != name:
        raise ValueError(f"metrics/{name}.py declares NAME={getattr(mod, 'NAME', None)!r}")
    return mod


def cell_metrics(bm: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """The end-to-end and per-layer metric entries that ``cell`` reports."""
    e2e = [m for m in bm["end_to_end"] if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    per_layer = [
        m for m in bm["per_layer"]
        if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)
    ]
    return e2e, per_layer
