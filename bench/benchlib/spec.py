"""Find a cell's pieces by name: its entry in BENCHMARK.json, its
configuration file, its traffic file, its limits file, the family
adapter and plain reference of its configuration and the readers of its
per-layer metrics."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
FAMILIES = BENCH / "families"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> dict:
    """Everything one cell needs: its workload entry, configuration,
    traffic, correctness limits, and the metrics it reports."""
    b = benchmark()
    found = [w for w in b["workloads"] if w["name"] == name]
    if not found:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{[w['name'] for w in b['workloads']]}")
    w = found[0]
    conf = [c for c in b["configs"] if c["name"] == w["config"]][0]
    return {
        "workload": w,
        "config": load_json(ROOT / conf["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        "limits": load_json(BENCH / "checks" / f"{name}.json"),
        "end_to_end": b["end_to_end"],
        "per_layer": b["per_layer"],
    }


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return _module(BENCH / "metrics" / f"{metric}.py",
                   f"bench_metric_{metric}").read


def reference_module(name: str):
    """The plain reference ``bench/reference/<name>.py``."""
    return _module(BENCH / "reference" / f"{name}.py", f"bench_ref_{name}")


def family(config: dict):
    """The family adapter ``<FAMILIES>/<name>.py`` of a configuration,
    named by its ``family`` key (``"unet"`` where absent)."""
    name = config.get("family", "unet")
    return _module(FAMILIES / f"{name}.py", f"bench_family_{name}")
