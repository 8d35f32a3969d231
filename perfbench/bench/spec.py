"""Find a cell's files by the names in ``BENCHMARK.json``."""

from __future__ import annotations

import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> dict:
    """The cell ``name`` with its configuration, traffic mix, limits, and
    the end-to-end and per-layer metrics it reports."""
    bm = benchmark()
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; know {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    cfg_entry = configs[w["config"]]

    def reported(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {
        "name": name,
        "chips": int(w["chips"]),
        "config": load_json(ROOT / cfg_entry["file"]),
        "traffic": load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        "limits": load_json(BENCH_DIR / "limits" / f"{w['config']}.json"),
        "end_to_end": reported(bm["end_to_end"]),
        "per_layer": reported(bm["per_layer"]),
    }


def layer_maps() -> dict[str, list[str]]:
    """Layer key (file stem) -> XLA module-name prefixes of that layer."""
    return {
        p.stem: load_json(p)["modules"]
        for p in sorted((BENCH_DIR / "layers").glob("*.json"))
    }


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; a kind missing from
    the table is an error, never a default."""
    table = load_json(BENCH_DIR / "bench" / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]
