"""CPU fixtures for the benchmark's own tests (run by hand:
``JAX_PLATFORMS=cpu python -m pytest bench/tests``; the repo's tier-1
collects ``tests/`` only)."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def shrink_sweep(t: dict) -> None:
    """A what-if grid small enough for the Pallas interpreter."""
    t["grid"]["loss_p"]["draws"] = 2
    t["grid"]["rate_scale"]["draws"] = 3
    t["check"] = {"rows_per_call": 48}


def shrink_gateway(t: dict) -> None:
    t["sessions"] = 120
    t["rate_per_s"] = 150.0
    t["burst"]["warm_s"] = 0.5
    t["burst"]["warm_states"] = 3
    t["check"] = {"sample_observes": 200}


@pytest.fixture(scope="session")
def pending_root(tmp_path_factory):
    """A checkout whose ``BENCHMARK.json`` also holds the entries of
    ``bench/pending/*.json`` (cells kept out of the benchmark until their
    runs hold a bound), with the data files they name."""
    import json
    import shutil

    root = tmp_path_factory.mktemp("pending")
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    for f in sorted((ROOT / "bench" / "pending").glob("*.json")):
        for key, entries in json.loads(f.read_text()).items():
            doc[key].extend(entries)
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    for sub in ("configs", "traffic", "checks"):
        shutil.copytree(ROOT / "bench" / sub, root / "bench" / sub)
    return root


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    """Keep CPU test runs out of the checkout's compile cache."""
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_enable_compilation_cache", False)
    yield
