"""Run one benchmark cell on the chips of this machine and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from BENCHMARK.json (bench/spec.py). With ``--trace 0`` the result
carries the cell's end-to-end metrics; with ``--trace 1`` a profiler trace
of the window is reduced to its per-layer metrics. The last line of
standard output is one JSON object; the numbers compared for ``correct``
come last in it (``checks``) and again as the last lines on standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def devices_ok(chips: int) -> bool:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"needs {chips} TPU chip(s); JAX has {len(devs)} "
            f"{devs[0].platform} device(s)")
        return False
    return True


def execute(cell, seed: int, seconds: float, trace: bool, *,
            control: bool = False, peak=None, t_process: float = T_PROCESS):
    """Run a cell (bench.spec.Cell) and return the result object. The
    caller has checked the devices; ``peak`` defaults to the peaks of the
    device's kind."""
    import importlib

    import jax

    from bench import flops, spec

    dev = jax.devices()[0]
    peak = peak or flops.peaks(dev.device_kind)
    runner = importlib.import_module(f"bench.{cell.config['runner']}")
    rec, checks, extra = runner.run(
        cell, seed, seconds, trace=trace, t_process=t_process, peak=peak,
        limits=cell.limits, control=control, log=log)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        mod = spec.metric_module(m["name"])
        if hasattr(mod, "samples"):
            log(f"{m['name']}: {len(mod.samples(rec))} samples")
        v = mod.read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": extra.pop("memory_peak_bytes")}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": extra.pop("attempted"), "failed": 0,
           "metrics": metrics, "device": device}
    if trace:
        s = rec.trace
        device["busy_s"] = s.busy_s()
        device["window_s"] = s.window_s
        out["breakdown"] = {"device_ops": s.top("modules"),
                            "idle_gaps": s.idle_gaps()}
    out["extra"] = extra
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="check the float8 control's readings in the "
                    "program's place, the program's going to extra "
                    "(calibration only)")
    a = ap.parse_args(argv)

    from bench import spec

    cell = spec.resolve(a.workload)
    if not devices_ok(cell.chips):
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    out = execute(cell, a.seed, a.seconds, bool(a.trace),
                  control=bool(a.control))
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
