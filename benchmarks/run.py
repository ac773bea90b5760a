"""Benchmark harness: one module per paper table/figure. CSV to stdout.

Exits non-zero if ANY module fails, so CI smoke runs can gate on it.
``--json [DIR]`` directs modules that support it (sim_throughput) to write
their BENCH_<module>.json snapshots into DIR (default: the repo root, so
a plain ``--json`` refreshes the committed baselines in place).

``--jobs N`` fans the modules out over N worker processes (spawn): each
worker runs one module with stdout/stderr captured, and the parent prints
the captured output in submission order, so the CSV stays deterministic.
A crashed worker fails the run non-zero just like an in-process exception.
Each worker imports JAX, and a TPU belongs to one process at a time, so
``--jobs N`` with N > 1 is refused unless ``JAX_PLATFORMS=cpu``: on a chip
run the modules in one process.

``--policy NAME`` / ``--hw NAME`` run the figure suites under a registered
memory-policy backend / hardware model (see repro.core.registry), e.g.

    python benchmarks/run.py --policy mi300a_unified --hw mi300a

Only modules whose ``run()`` accepts the overrides participate (currently
the AppSpec-driven fig3 suite); the others are skipped with a note, since
silently running them on the default backend would mislabel the results.
Skip detection happens in the parent, so with ``--jobs`` each skip is
still reported exactly once.
"""
import contextlib
import importlib
import inspect
import io
import multiprocessing
import os
import sys
import traceback
from concurrent.futures import ProcessPoolExecutor
from concurrent.futures.process import BrokenProcessPool
from pathlib import Path

from benchmarks.common import header

REPO_ROOT = Path(__file__).resolve().parent.parent

MODULES = [
    "benchmarks.fig3_overview",
    "benchmarks.fig45_timeline",
    "benchmarks.fig67_pagesize",
    "benchmarks.fig89_qiskit",
    "benchmarks.fig10_srad_migration",
    "benchmarks.fig11_oversub",
    "benchmarks.fig1213_prefetch",
    "benchmarks.lm_serve_paged",
    "benchmarks.lm_roofline",
    "benchmarks.sim_throughput",
    "benchmarks.train_oversub",
]


def _pop_value_flag(argv: list, flag: str):
    """Remove ``flag VALUE`` from argv and return VALUE (or None)."""
    if flag not in argv:
        return None
    i = argv.index(flag)
    argv.pop(i)
    if i >= len(argv) or argv[i].startswith("-"):
        print(f"benchmarks/run.py: {flag} needs a value", file=sys.stderr)
        raise SystemExit(2)
    return argv.pop(i)


def _takes_overrides(m: str, overrides: dict) -> bool:
    """Whether module m's run() accepts every override kwarg."""
    params = inspect.signature(importlib.import_module(m).run).parameters
    if any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()):
        return True
    return all(k in params for k in overrides)


def _run_module(m: str, overrides: dict):
    """Worker: import + run one module with stdout/stderr captured (the
    fan-out would interleave them otherwise). Returns (stdout, stderr,
    traceback-or-None); the parent replays the streams in order."""
    out, err = io.StringIO(), io.StringIO()
    error = None
    try:
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            importlib.import_module(m).run(**overrides)
    except Exception:
        error = traceback.format_exc()
    return out.getvalue(), err.getvalue(), error


def main(argv=None) -> int:
    """Run all (or the named) benchmark modules; return a shell exit code."""
    argv = list(argv) if argv else []
    # value-taking flags first, so --json's optional-DIR sniffing below can
    # never swallow them as its directory argument
    policy = _pop_value_flag(argv, "--policy")
    hw = _pop_value_flag(argv, "--hw")
    jobs_s = _pop_value_flag(argv, "--jobs")
    try:
        jobs = max(1, int(jobs_s)) if jobs_s is not None else 1
    except ValueError:
        print(f"benchmarks/run.py: --jobs needs an integer, got {jobs_s!r}",
              file=sys.stderr)
        raise SystemExit(2)
    if jobs > 1 and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("benchmarks/run.py: --jobs > 1 needs JAX_PLATFORMS=cpu (each "
              "worker imports JAX, and only one process may hold a TPU)",
              file=sys.stderr)
        raise SystemExit(2)
    if "--json" in argv:
        i = argv.index("--json")
        argv.pop(i)
        if (i < len(argv) and not argv[i].startswith("benchmarks.")
                and not argv[i].startswith("-")):
            os.environ["BENCH_JSON_DIR"] = argv.pop(i)
        else:
            os.environ.setdefault("BENCH_JSON_DIR", str(REPO_ROOT))
    overrides = {}
    if policy is not None:
        overrides["policy"] = policy
    if hw is not None:
        overrides["hw"] = hw
    names = argv if argv else MODULES
    header()
    failed = []
    todo = []
    for m in names:
        # skip detection stays in the parent: one note per module, never
        # repeated per worker
        if overrides and not _takes_overrides(m, overrides):
            print(f"# {m}: skipped (run() takes no "
                  f"{'/'.join(overrides)} overrides)", file=sys.stderr)
            continue
        todo.append(m)
    if jobs > 1:
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(max_workers=jobs, mp_context=ctx) as ex:
            futs = [(m, ex.submit(_run_module, m, overrides)) for m in todo]
            for m, f in futs:
                try:
                    out, err, error = f.result()
                except BrokenProcessPool:
                    failed.append(m)
                    print(f"# {m}: worker process crashed", file=sys.stderr)
                    continue
                sys.stdout.write(out)
                sys.stderr.write(err)
                if error is not None:
                    failed.append(m)
                    sys.stderr.write(error)
    else:
        for m in todo:
            try:
                importlib.import_module(m).run(**overrides)
            except Exception:
                failed.append(m)
                traceback.print_exc()
    if failed:
        print(f"benchmark failures: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
