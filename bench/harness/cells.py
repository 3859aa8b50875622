"""Find a cell's configuration, traffic mix and metrics by name.

``BENCHMARK.json`` at the checkout root names each cell's config and
traffic; the files are ``bench/configs/<config>.json`` and
``bench/traffic/<traffic>.json``.  A configuration names its model
family under ``"family"``, which is ``bench/families/<family>.py``, and
its plain reference under ``"reference"``, which is
``bench/references/<reference>.py``.  A per-layer metric is
``bench/metrics/<metric>.py``.  Adding any of them is adding files and
entries; no existing file changes.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]


class CellError(ValueError):
    """A cell, configuration, traffic mix or metric that cannot be found
    or is malformed."""


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(bench_dir: Path, kind: str, name: str):
    """``<bench_dir>/<kind>/<name>.py``, loaded as a fresh module."""
    path = Path(bench_dir) / kind / f"{name}.py"
    if not path.is_file():
        raise CellError(f"no {kind} module {path}")
    mod_name = f"bench_{kind}_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    # registered before it runs, as an import would be: a dataclass looks
    # its module up while the class is made
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Benchmark:
    """``BENCHMARK.json`` and the bench directory beside it."""

    def __init__(self, bench_dir: Optional[Path] = None,
                 spec: Optional[Dict[str, Any]] = None):
        self.dir = Path(bench_dir if bench_dir is not None else BENCH_DIR)
        self.spec = (spec if spec is not None
                     else load_json(self.dir.parent / "BENCHMARK.json"))

    def workload(self, name: str) -> Dict[str, Any]:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise CellError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict[str, Any]:
        path = self.dir / "configs" / f"{name}.json"
        if not path.is_file():
            raise CellError(f"no configuration file {path}")
        spec = load_json(path)
        if "family" not in spec:
            raise CellError(f"configuration {path} names no family")
        spec.setdefault("name", name)
        return spec

    def traffic(self, name: str) -> Dict[str, Any]:
        path = self.dir / "traffic" / f"{name}.json"
        if not path.is_file():
            raise CellError(f"no traffic file {path}")
        spec = load_json(path)
        spec.setdefault("name", name)
        return spec

    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.spec["end_to_end"] if _applies(m, cell)]

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.spec["per_layer"] if _applies(m, cell)]

    def metric_reader(self, name: str):
        """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
        return load_module(self.dir, "metrics", name).read

    def reference(self, name: str):
        """The plain reference module ``bench/references/<name>.py``."""
        return load_module(self.dir, "references", name)

    def family(self, name: str):
        """The model family module ``bench/families/<name>.py``."""
        return load_module(self.dir, "families", name)
