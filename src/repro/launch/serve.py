"""Serving launcher: batched paged-KV serving of an --arch model.

Example (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
      --requests 4 --max-new 8

``serve()`` is the launcher's body as a function: chip_smoke.py drives the
served path through it.
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import UnifiedMemory, TPU_V5E
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serve import ServeEngine


def serve(arch: str, *, reduced: bool = False, requests: int = 4,
          prompt_len: int = 24, max_new: int = 8, page_size: int = 16,
          max_len: int = 128, umem: bool = False,
          seed: int = 0) -> Dict[str, Any]:
    """Build the model (bf16 random weights from ``seed``) and a
    ServeEngine, serve ``requests`` random prompts of ``prompt_len`` +- 4
    tokens, and return what main() prints.

    ``umem`` puts the KV pool under a UnifiedMemory runtime modeling one
    TPU v5e, which then also gates admission."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    assert cfg.mixer == "attention", "paged serving targets attention archs"
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.bfloat16)
    um = UnifiedMemory(hw=TPU_V5E) if umem else None
    eng = ServeEngine(cfg, params, max_seqs=max(4, requests),
                      max_len=max_len, page_size=page_size, um=um)
    rng = np.random.default_rng(seed)
    for _ in range(requests):
        plen = max(2, prompt_len + int(rng.integers(-4, 5)))
        eng.add_request(rng.integers(2, cfg.vocab_size, plen), max_new)
    t0 = time.perf_counter()
    out = eng.run_to_completion()
    wall = time.perf_counter() - t0
    return {
        "arch": arch,
        "vocab_size": cfg.vocab_size,
        "prompt_lens": [len(r.prompt) for r in eng.requests.values()],
        "outputs": out,
        "tokens": sum(len(v) for v in out.values()),
        "wall_s": wall,
        "prefill_chunks": eng.stats.prefill_chunks,
        "umem_traffic": um.report()["traffic_total"] if um is not None else None,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--umem", action="store_true",
                    help="track the KV pool in the unified-memory runtime")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    run = serve(**vars(args))
    out = run["outputs"]
    print(f"arch={args.arch} requests={len(out)} tokens={run['tokens']} "
          f"wall={run['wall_s']:.2f}s tok/s={run['tokens'] / run['wall_s']:.1f}")
    for rid, toks in sorted(out.items()):
        print(f"  req {rid}: {toks}")
    if run["umem_traffic"] is not None:
        print("umem:", run["umem_traffic"])


if __name__ == "__main__":
    main()
