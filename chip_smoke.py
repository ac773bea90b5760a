"""Smoke test of the main paths on a TPU, through the launchers' entry points.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # four chips: the sharded train step only

One chip:
  1. the compiled paged-attention decode kernel at yi-6b decode widths
     against its pure-jnp reference;
  2. yi-6b, all 32 layers at published widths with bf16 weights from a seed,
     served by ``repro.launch.serve.serve``: 4 requests of 380-388 prompt
     tokens (several 128-token prefill chunks each), 32 new tokens each,
     over a 64-token-page KV pool governed by the unified-memory runtime.

Four chips: ``repro.launch.train.train``'s step for yi-6b at published widths,
depth cut to 1 layer, on a (data=2, model=2) mesh and on one chip unsharded,
same seed and batch; the losses after 2 steps must agree.

Exits non-zero without a result line when JAX finds no TPU, and when any
phase fails. On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache/``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.clock import compile_clock  # noqa: E402

ARCH = "yi-6b"
# paged_attention returns q's dtype: the bf16 output rounds by up to 2^-9
# relative, and Mosaic may take the softmax weights through one bf16 pass in
# the PV matmul (another 2^-9 of sum |p v|). With N(0,1) values |o| <= max|v|
# ~ 5, so the bound is about 5 * 2 * 2^-9 ~ 0.02 (the bf16 tolerance of the
# CPU kernel tests).
KERNEL_ATOL = 2e-2
# The sharded and the unsharded step run at matmul precision "highest", so
# their losses differ only by the order of the reductions across chips: ~1e-6
# relative. A sharding fault (a missing or doubled all-reduce) moves the loss
# by O(1).
LOSS_RTOL = 1e-3


def check(ok: bool, what) -> None:
    """A failed check ends the run (unlike assert, also under python -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def check_paged_attention() -> None:
    from repro.kernels.common import default_interpret
    from repro.kernels.paged_attention import paged_attention, paged_attention_ref

    check(not default_interpret(), "the kernel would run interpreted")
    B, H, Hkv, D, PS, NP = 8, 32, 4, 128, 64, 8
    P = B * NP + 1
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, H, D), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (P, Hkv, PS, D), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (P, Hkv, PS, D), jnp.bfloat16)
    pt = (1 + jax.random.permutation(ks[3], P - 1)).reshape(B, NP).astype(jnp.int32)
    lengths = jax.random.randint(ks[4], (B,), 1, NP * PS + 1, jnp.int32)
    out = jax.block_until_ready(paged_attention(q, kp, vp, pt, lengths))
    with jax.default_matmul_precision("highest"):
        ref = paged_attention_ref(q, kp, vp, pt, lengths)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
    print(f"paged_attention B={B} H={H} Hkv={Hkv} D={D} page={PS} pages={NP} "
          f"bf16: max_abs_err={err!r} atol={KERNEL_ATOL} (bf16 output "
          "rounding plus one bf16 pass over the softmax weights)")
    check(np.isfinite(err) and err <= KERNEL_ATOL, (err, KERNEL_ATOL))


def serve_yi6b() -> None:
    from repro.launch.serve import serve

    requests, max_new = 4, 32
    run = serve(ARCH, requests=requests, prompt_len=384, max_new=max_new,
                page_size=64, max_len=1024, umem=True, seed=0)
    outs = run["outputs"]
    check(len(outs) == requests, outs.keys())
    for rid, toks in outs.items():
        check(len(toks) == max_new, (rid, len(toks)))
        check(all(0 <= t < run["vocab_size"] for t in toks), (rid, toks))
    check(run["prefill_chunks"] > requests, run["prefill_chunks"])
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"serve {ARCH} (32 layers, bf16): requests={len(outs)} "
          f"prompt_lens={run['prompt_lens']} tokens={run['tokens']} "
          f"prefill_chunks={run['prefill_chunks']} wall_s={run['wall_s']!r} "
          f"peak_bytes_in_use={peak}")


def train_sharded_vs_one_chip() -> None:
    from repro.launch.train import train

    kw = dict(layers=1, steps=2, batch=4, seq=512, seed=0)
    with jax.default_matmul_precision("highest"):
        sharded = train(ARCH, data=2, model=2, **kw)["losses"]
        single = train(ARCH, **kw)["losses"]
    print(f"train {ARCH} depth 1 (cut from 32), batch 4x512, 2 steps: "
          f"losses data=2,model=2: {sharded!r}  one chip: {single!r}  "
          f"rtol={LOSS_RTOL}")
    check(np.all(np.isfinite(sharded + single)), (sharded, single))
    check(np.allclose(sharded, single, rtol=LOSS_RTOL, atol=0), (sharded, single))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX finds no TPU (backend "
              f"{jax.default_backend()!r}); nothing runs on the CPU",
              file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"JAX finds {len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    clock = compile_clock()
    phases = ([train_sharded_vs_one_chip] if args.chips == 4
              else [check_paged_attention, serve_yi6b])
    for phase in phases:
        t0, c0 = time.perf_counter(), clock.seconds
        phase()
        print(f"phase {phase.__name__}: wall_s={time.perf_counter() - t0!r} "
              f"compile_s={clock.seconds - c0!r}")
    print(f"compile_s_total={clock.seconds!r} "
          f"persistent_cache_hits={clock.cache_hits}")
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
