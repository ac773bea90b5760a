"""Training launcher: --arch <id> on the local device set (or a fake mesh).

Example (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \
      --steps 50 --batch 4 --seq 64 --ckpt-dir /tmp/ckpt

``train()`` is the launcher's body as a function: chip_smoke.py --chips 4
compares its sharded and unsharded runs through it.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import DataLoader, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import make_run_policy, param_specs
from repro.launch.steps import _named
from repro.models import init_params
from repro.models.layers import RunPolicy
from repro.models.transformer import set_policy_tp
from repro.runtime import FailureInjector
from repro.train import Trainer, TrainerConfig, make_train_state, make_train_step


def train(arch: str, *, reduced: bool = False, layers: int = 0,
          steps: int = 100, batch: int = 8, seq: int = 128, accum: int = 1,
          lr: float = 3e-4, ckpt_dir: Optional[str] = None,
          ckpt_every: int = 50, data: int = 1, model: int = 1,
          fail_at: Sequence[int] = (), compress_grads: bool = False,
          seed: int = 0) -> Dict[str, Any]:
    """Train ``steps`` steps on a (data, model) mesh of the local devices
    (one device, unsharded, when both are 1) and return the history.

    ``layers`` > 0 cuts the depth to that many layers; widths stay."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    if layers:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    n_dev = data * model
    assert n_dev <= len(jax.devices()), (n_dev, len(jax.devices()))

    mesh = make_host_mesh(data=data, model=model) if n_dev > 1 else None
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32,
                         tp=model)
    if mesh is not None:
        params = jax.device_put(params, _named(mesh, param_specs(params, mesh)))
        policy = make_run_policy(mesh, remat=True)
    else:
        policy = set_policy_tp(RunPolicy(remat=True), 1)

    state = make_train_state(cfg, params)
    tc = TrainerConfig(lr=lr, total_steps=steps,
                       warmup_steps=max(1, steps // 10), grad_accum=accum,
                       tp=model, compress_grads=compress_grads)
    # the state is donated: the step updates it in place, so the old and the
    # new state never have to fit on the device together
    step = jax.jit(make_train_step(cfg, policy, tc), donate_argnums=0)
    if mesh is not None:
        _step = step

        def step(s, b):
            with mesh:
                return _step(s, b)

    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq,
                     global_batch=batch, seed=seed,
                     emb_dim=cfg.d_model if cfg.input_kind == "embeddings" else 0)
    loader = DataLoader(ds)
    ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
    injector = FailureInjector.at(fail_at) if fail_at else None
    trainer = Trainer(cfg, state, step, loader, ckpt=ckpt,
                      injector=injector, ckpt_every=ckpt_every)
    try:
        out = trainer.run(steps)
    finally:
        loader.close()
    return {"arch": arch, "devices": n_dev, "restarts": out["restarts"],
            "losses": [h["loss"] for h in out["history"]],
            "dts": [h["dt"] for h in out["history"]]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to this many layers (0: published)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", type=int, default=1, help="data-parallel axis")
    ap.add_argument("--model", type=int, default=1, help="tensor-parallel axis")
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    run = train(**vars(args))
    losses = run["losses"]
    print(f"arch={args.arch} steps={len(losses)} restarts={run['restarts']} "
          f"first_loss={losses[0]:.4f} last_loss={losses[-1]:.4f} "
          f"mean_dt={np.mean(run['dts']):.3f}s")


if __name__ == "__main__":
    main()
