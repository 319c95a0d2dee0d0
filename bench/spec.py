"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

* ``bench/configs/<config>.json`` - the deployment (``configs[].file``);
* ``bench/traffic/<traffic>.json`` - the traffic mix, naming its driver;
* ``bench/drivers/<driver>.py`` - the generator and the system calls;
* ``bench/metrics/<metric>.py`` - one reader per metric (``read(run)``);
* ``bench/checks/<cell>.json`` - the limit of each number compared.

Entries of a cell kept out of ``BENCHMARK.json`` until its runs hold a
bound wait in ``bench/pending/<cell>.json``, in the same layout.

Data files are found under the benchmark's root (the checkout), code
(drivers, readers) beside this file. A new cell needs new files and new
entries only: nothing here changes.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def enable_cache() -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    (set before JAX starts, by whoever runs the cell), through the
    program's own helper, keeping every program however quick."""
    import os

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    os.makedirs(path, exist_ok=True)  # JAX writes entries, not the directory
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Benchmark:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.cells = {w["name"]: w for w in self.doc["workloads"]}

    def cell(self, name: str) -> "Cell":
        if name not in self.cells:
            raise KeyError(f"unknown workload {name!r}; known: {sorted(self.cells)}")
        return Cell(self, self.cells[name])

    def metrics_for(self, cell: str, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
        return [m for m in self.doc[kind]
                if "workloads" not in m or cell in m["workloads"]]


class Cell:
    def __init__(self, bench: Benchmark, entry: dict):
        self.bench = bench
        self.name = entry["name"]
        self.chips = int(entry["chips"])
        root = bench.root
        cfg_entry = bench.configs[entry["config"]]
        self.config = json.loads((root / cfg_entry["file"]).read_text())
        self.traffic = json.loads(
            (root / "bench" / "traffic" / f"{entry['traffic']}.json").read_text())
        self.limits = json.loads(
            (root / "bench" / "checks" / f"{self.name}.json").read_text())["limits"]

    def driver(self):
        name = self.traffic["driver"]
        if not (BENCH / "drivers" / f"{name}.py").is_file():
            raise FileNotFoundError(f"missing bench/drivers/{name}.py")
        return importlib.import_module(f"bench.drivers.{name}")

    def readers(self, kind: str) -> dict:
        out = {}
        for m in self.bench.metrics_for(self.name, kind):
            if m["name"] == "setup_s":
                continue
            path = BENCH / "metrics" / f"{m['name']}.py"
            out[m["name"]] = (m, _load_module(path, f"bench.metrics.{m['name']}"))
        return out
