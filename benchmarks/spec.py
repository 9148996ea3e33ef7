"""Finds everything a cell is made of by the names in BENCHMARK.json.

A cell (one entry of `workloads`) names a configuration and a traffic
mix.  Each lives in a file of its own under this directory, and so do
the generator a mix is read by, the driver of a deployment kind and the
reader of a per-layer metric:

    configs/<config>.json          sizes as run, source, reduced, assumed,
                                   and `deployment` (kind + its arguments)
    traffic/<traffic>.json         parameters of the mix, `generator` by name
    generators/<generator>.py      `generate(params, seed, seconds, ...)`
    kinds/<kind>.py                `run(cell, args)`: brings the deployment
                                   up through the cluster and measures it
    layer_metrics/<metric>.json    layer, unit, moves, `reader` by name,
                                   the reader's parameters
    readers/<reader>.py            `read(obs, params)` -> number or None

A later PR adds a cell, a mix or a metric by adding files and an entry in
BENCHMARK.json; nothing here is edited.  `root` is a parameter so that a
test can show that from a temporary directory.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
import sys
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _load_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"{path}: {e}") from None


def _checked(name: str, what: str) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise SpecError(f"{what} {name!r} is not a name (letters, digits, "
                        f"'_', '.', '-'; at most 64)")
    return name


def _load_module(path: str, attr: str) -> Callable:
    """`attr` of the Python file at `path`.  A file of this package is
    imported under its own name, so that what it defines pickles by
    reference into a worker; any other (a test's temporary directory) is
    loaded from its location and pickled by value."""
    if not os.path.isfile(path):
        raise SpecError(f"{path}: no such file")
    rel = os.path.relpath(os.path.abspath(path), HERE)
    if re.fullmatch(r"[A-Za-z_]\w*(/[A-Za-z_]\w*)*\.py", rel):
        module = importlib.import_module(
            "benchmarks." + rel[:-3].replace("/", "."))
    else:
        modname = "benchmarks_found_" + re.sub(r"\W", "_", path)
        module = sys.modules.get(modname)
        if module is None:
            found = importlib.util.spec_from_file_location(modname, path)
            module = importlib.util.module_from_spec(found)
            sys.modules[modname] = module
            found.loader.exec_module(module)
            import cloudpickle

            cloudpickle.register_pickle_by_value(module)
    fn = getattr(module, attr, None)
    if not callable(fn):
        raise SpecError(f"{path} defines no `{attr}`")
    return fn


class Spec:
    """BENCHMARK.json of `repo` and the benchmark directory `root`."""

    def __init__(self, repo: str = REPO, root: Optional[str] = None):
        self.repo = repo
        self.root = root or os.path.join(repo, "benchmarks")
        self.benchmark = _load_json(os.path.join(repo, "BENCHMARK.json"))

    # ------------------------------------------------------------ entries

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.benchmark["workloads"]:
            if w["name"] == name:
                return w
        known = [w["name"] for w in self.benchmark["workloads"]]
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(it has {known})")

    def config(self, name: str) -> Dict[str, Any]:
        """The configuration's file, as BENCHMARK.json names it."""
        for c in self.benchmark["configs"]:
            if c["name"] == name:
                return _load_json(os.path.join(self.repo, c["file"]))
        raise SpecError(f"no configuration {name!r} in BENCHMARK.json")

    def _find(self, sub: str, filename: str) -> str:
        """`<root>/<sub>/<filename>`, or this package's where `root` is
        another directory and has none (a PR's new files beside the old)."""
        for base in (self.root, HERE):
            path = os.path.join(base, sub, filename)
            if os.path.isfile(path):
                return path
        raise SpecError(f"no {sub}/{filename} under {self.root}")

    def traffic(self, name: str) -> Dict[str, Any]:
        return _load_json(self._find(
            "traffic", _checked(name, "traffic mix") + ".json"))

    def generator_file(self, name: str) -> str:
        return self._find("generators", _checked(name, "generator") + ".py")

    def generator(self, name: str) -> Callable:
        return _load_module(self.generator_file(name), "generate")

    def kind(self, name: str) -> Callable:
        return _load_module(self._find(
            "kinds", _checked(name, "deployment kind") + ".py"), "run")

    # ------------------------------------------------------------ metrics

    def metrics_of(self, group: str, cell: str) -> List[Dict[str, Any]]:
        """The `end_to_end` or `per_layer` metrics this cell reports."""
        return [m for m in self.benchmark[group]
                if "workloads" not in m or cell in m["workloads"]]

    def read_layer_metrics(self, cell: str, obs: Dict[str, Any]
                           ) -> Dict[str, Dict[str, Any]]:
        """Every per-layer metric of the cell whose reader finds
        something to read; one that finds nothing is left out."""
        out = {}
        for m in self.metrics_of("per_layer", cell):
            path = self._find("layer_metrics",
                              _checked(m["name"], "metric") + ".json")
            meta = _load_json(path)
            for key in ("unit", "layer", "moves"):
                if meta.get(key) != m[key]:
                    raise SpecError(
                        f"{path}: {key} {meta.get(key)!r} is not "
                        f"BENCHMARK.json's {m[key]!r}")
            read = _load_module(self._find(
                "readers", _checked(meta["reader"], "reader") + ".py"),
                "read")
            value = read(obs, meta.get("params", {}))
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out
