"""Run one cell traced, as ``bench/run.py --trace 1`` does, and split the
device's idle time by the program's own host spans.

    python3 bench/split_run.py --workload <cell> --seed <n> --seconds <s>

The last line of standard output is bench/run.py's result object with one
more key, ``split``:

- ``idle_in.<bucket>``: idle seconds in each bucket of bench/program_spans.py
  over the traced window's seconds, in %; they add up to ``idle_share``;
- ``ttft_queue_p50_ms`` and ``ttft_prefill_p50_ms``: over the requests whose
  first token came in the window, the medians of the engine's wall stamps
  ``prefill_wall - arrival_wall`` and ``first_token_wall - prefill_wall``;
- ``output_tok_s``: tokens per second of the traced window, to set against
  an untraced run of the same seed;
- ``reduce_s``: the seconds of the harness's reduction (reading the trace,
  then the metrics and breakdown bench/run.py computes from it) and of the
  split (reading the program's spans, then the sweep).

bench/run.py and the modules it calls stay as they are: this script wraps
``trace_reduce.reduce_file`` to keep the trace's spans, ``serve.build_engine``
to keep the engine's requests, and ``serve.run`` to keep the run record,
for the one process it runs in.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run as bench_run  # noqa: E402  (puts src on the path)


def median_ms(xs):
    return statistics.median(xs) * 1e3 if xs else None


def split(kept):
    """The ``split`` object from what the wrappers in ``main`` kept."""
    from bench import program_spans
    from bench.spec import metric_module

    rec = kept["rec"]
    s = rec.trace
    t = time.perf_counter()
    out = program_spans.idle_in(s, kept["spans"])
    split_s = kept["read_s"] + time.perf_counter() - t
    out["idle_share"] = 100.0 * s.idle_share()
    t0, t1 = rec.window
    first = [r for r in kept["requests"].values()
             if r.first_token_wall is not None
             and t0 <= r.first_token_wall <= t1]
    out["ttft_queue_p50_ms"] = median_ms(
        [r.prefill_wall - r.arrival_wall for r in first])
    out["ttft_prefill_p50_ms"] = median_ms(
        [r.first_token_wall - r.prefill_wall for r in first])
    out["first_tokens"] = len(first)
    out["output_tok_s"] = metric_module("output_tok_s").read(rec)
    out["program_spans"] = len(kept["spans"])
    out["reduce_s"] = {"harness": kept["reduce_s"] + kept["metrics_s"],
                       "split": split_s}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)

    from bench import program_spans, serve, spec, trace_reduce

    cell = spec.resolve(a.workload)
    if not bench_run.devices_ok(cell.chips):
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    kept = {}
    reduce_file, build_engine, serve_run = (
        trace_reduce.reduce_file, serve.build_engine, serve.run)

    def reduce_keeping_spans(path, window=None):
        t = time.perf_counter()
        summary = reduce_file(path, window)
        t1 = time.perf_counter()
        kept["spans"] = program_spans.read_spans(path)
        kept["reduce_s"], kept["read_s"] = t1 - t, time.perf_counter() - t1
        return summary

    def build_keeping_requests(cfg, params):
        eng = build_engine(cfg, params)
        kept["requests"] = eng.requests
        return eng

    def run_keeping_record(*args, **kw):
        rec, checks, extra = serve_run(*args, **kw)
        kept["rec"], kept["returned"] = rec, time.perf_counter()
        return rec, checks, extra

    trace_reduce.reduce_file = reduce_keeping_spans
    serve.build_engine = build_keeping_requests
    serve.run = run_keeping_record
    out = bench_run.execute(cell, a.seed, a.seconds, True,
                            t_process=T_PROCESS)
    # what bench/run.py computes from the trace once the run has returned
    kept["metrics_s"] = time.perf_counter() - kept["returned"]
    out["split"] = split(kept)
    for name, c in out["checks"].items():
        bench_run.log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
