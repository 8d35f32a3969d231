#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``perfbench/configs/``), a traffic mix (``perfbench/traffic/``) and, by its
configuration, the limits of its correctness check (``perfbench/limits/``).
One process drives the repository's serving entry points on one chip.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` records a
profiler trace of the window and reports its per-layer metrics, each read
by its own reader (``perfbench/metrics/<name>.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``checks``, every compared number beside its limit. Those
numbers also end standard error. The run exits non-zero with no result when
JAX finds no TPU or fewer chips than the cell asks for, or when the
repository's ``src/`` is missing.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is measured from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
OUT_DIR = ROOT / ".bench_out"  # traces; listed in .gitignore
sys.path.insert(0, str(BENCH_DIR))

from bench import spec  # noqa: E402


class Tracer:
    """The profiler around a window; ``window_s`` is the traced span."""

    def __init__(self, path: Path) -> None:
        shutil.rmtree(path, ignore_errors=True)
        self.path = path

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(str(self.path))
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        self.window_s = time.perf_counter() - self.t0
        import jax

        jax.profiler.stop_trace()


def load_reader(name: str):
    """The per-layer metric ``name``'s reader: ``read(ctx) -> float | None``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def finite(v: float) -> float:
    """JSON has no infinity: the largest double stands in for it."""
    return max(min(float(v), sys.float_info.max), -sys.float_info.max)


def compare(numbers: dict, limits: dict) -> list[dict]:
    """Each number beside its limit; a number passes at or under it."""
    return [{"name": k, "value": finite(v), "limit": float(limits[k]),
             "ok": bool(float(v) <= float(limits[k]))}
            for k, v in numbers.items()]


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def run_cell(cell: dict, seed: int, seconds: float, trace: bool) -> dict:
    """Drive one run of ``cell`` and assemble its result line (without the
    device check: ``main`` makes it, tests drive this on the CPU)."""
    from bench import compiles, serve, trace as trace_mod

    cell = dict(cell, compiles=compiles.CompileCounter(),
                memory_peak=lambda: memory_peak(cell["chips"]))
    tracer = Tracer(OUT_DIR / "trace" / cell["name"]) if trace else None
    out = serve.run(cell, seed, seconds, tracer)
    # a limit the configuration states is set by the run (it may depend on
    # how many answers were checked); the others come from the limits file
    checks = compare(out["checks"], dict(cell["limits"], **out["limits"]))
    result = {
        "correct": all(c["ok"] for c in checks),
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": {},
        "device": dict(device_info(cell["chips"]),
                       memory_peak_bytes=out["memory_peak_bytes"]),
    }
    if tracer is None:
        values = dict(out["e2e"], setup_s=out["setup_end"] - T_START)
        for m in cell["end_to_end"]:
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        reduced = trace_mod.reduce_trace(trace_mod.find_xplane(str(tracer.path)),
                                         spec.layer_maps())
        ctx = dict(out["layer_ctx"], layer_ms=reduced["layer_ms"],
                   busy_s=reduced["busy_s"], window_s=tracer.window_s,
                   peaks=spec.peaks(result["device"]["kind"]))
        print(f"trace: busy {reduced['busy_s']:.6f} s of {tracer.window_s:.6f} s; "
              f"layer device ms {reduced['layer_ms']}", flush=True)
        for m in cell["per_layer"]:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["device"].update(busy_s=reduced["busy_s"], window_s=tracer.window_s)
        result["breakdown"] = trace_mod.breakdown(reduced)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]} for c in checks}
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr, flush=True)
    return result


def prepare(cell: dict) -> int:
    """Put the program on the path, keep the compile cache in the checkout,
    and make sure JAX sees the chips the cell asks for; non-zero if not."""
    src = ROOT / "src"
    if not (src / "repro" / "__init__.py").is_file():
        print(f"perfbench: the program is missing ({src / 'repro'}); run from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    # the compile cache lives in the checkout, whatever the environment says
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"perfbench: the cell needs {cell['chips']} TPU chip(s); JAX "
              f"sees {len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every executable goes to the cache, however short its compile: DROP's
    # are many and small, and a run's set-up should compile none of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    if prepare(cell) != 0:
        return 1
    info = device_info(cell["chips"])
    print(f"device: {info['kind']} x{info['count']} ({info['platform']})", flush=True)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
