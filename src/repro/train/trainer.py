"""Training loop: microbatched pjit train step + fault-tolerant driver.

make_train_step builds the jitted step:
  * gradient accumulation via lax.scan over microbatches (memory-bounded),
  * fp32 grad accumulators constrained to the ZeRO opt-state sharding
    (the per-microbatch psum lowers to reduce-scatter — ZeRO-2-style),
  * exactness hooks for the padded TP head layout (grad mask + KV-replica
    grad sync, models/transformer.py),
  * optional int8+error-feedback compression of the cross-pod gradient sync,
  * AdamW with fp32 master weights (ZeRO-1-sharded).

Trainer drives the loop: checkpoint cadence, failure recovery (restore +
deterministic data replay), straggler monitoring.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs.base import ArchConfig
from repro.models import grad_mask, loss_fn, sync_replica_grads
from repro.models.layers import RunPolicy
from repro.optim import adamw_init, adamw_update, ef_int8_roundtrip
from repro.optim.schedule import warmup_cosine
from repro.runtime import FailureInjector, SimulatedFailure, StragglerMonitor


@dataclass
class TrainerConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    grad_accum: int = 1
    ckpt_every: int = 50
    compress_grads: bool = False  # int8 + error feedback on the accumulated grads
    tp: int = 1


def make_train_state(cfg: ArchConfig, params) -> Dict[str, Any]:
    return {"params": params, "opt": adamw_init(params), "step": jnp.zeros((), jnp.int32)}


def split_microbatches(batch, accum: int):
    """(B, ...) leaves -> (accum, B/accum, ...); microbatch i takes rows
    i, i + accum, i + 2*accum, ...

    Splitting the batch axis as (B/accum, accum) and moving ``accum`` to the
    front keeps a 'data' sharding of the batch on the per-microbatch axis.
    The contiguous (accum, B/accum) split would put it on the leading axis,
    which ``lax.scan`` requires to be replicated."""
    return jax.tree.map(
        lambda x: jnp.moveaxis(
            x.reshape((x.shape[0] // accum, accum) + x.shape[1:]), 1, 0),
        batch)


def make_train_step(cfg: ArchConfig, policy: RunPolicy, tc: TrainerConfig,
                    grad_spec_constrain: Optional[Callable] = None):
    """Returns step(state, batch, [err]) -> (state, metrics[, err]).

    grad_spec_constrain(tree) applies with_sharding_constraint with the
    ZeRO spec to the grad accumulators (None = no constraint, single host).
    """
    lr_fn = warmup_cosine(tc.lr, tc.warmup_steps, tc.total_steps)
    constrain = grad_spec_constrain or (lambda t: t)
    mask = None  # built lazily against the param tree

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch, policy), has_aux=True)(params)
        return loss, metrics, grads

    def step(state, batch, err=None):
        params = state["params"]
        B = batch["labels"].shape[0]
        accum = tc.grad_accum
        assert B % accum == 0, (B, accum)

        if accum == 1:
            loss, metrics, grads = grads_of(params, batch)
            grads = constrain(jax.tree.map(lambda g: g.astype(jnp.float32), grads))
        else:
            def micro(carry, mb):
                gacc, lacc = carry
                l, m, g = grads_of(params, mb)
                gacc = constrain(jax.tree.map(
                    lambda a, gg: a + gg.astype(jnp.float32), gacc, g))
                return (gacc, lacc + l), None

            mb_tree = split_microbatches(batch, accum)
            gacc0 = constrain(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))
            (grads, loss_sum), _ = jax.lax.scan(micro, (gacc0, 0.0), mb_tree)
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss = loss_sum / accum
            metrics = {}

        # exact padded-TP hooks
        grads = sync_replica_grads(cfg, grads, tc.tp)
        m = grad_mask(cfg, params, tc.tp)
        grads = jax.tree.map(lambda g, mm: g * mm.astype(g.dtype), grads, m)

        new_err = err
        if tc.compress_grads and err is not None:
            flat_g, tdef = jax.tree.flatten(grads)
            flat_e = tdef.flatten_up_to(err)
            outs = [ef_int8_roundtrip(g, e) for g, e in zip(flat_g, flat_e)]
            grads = tdef.unflatten([o[0] for o in outs])
            new_err = tdef.unflatten([o[1] for o in outs])

        lr = lr_fn(state["step"])
        params, opt, gnorm = adamw_update(
            grads, state["opt"], params, lr=lr,
            weight_decay=tc.weight_decay, clip_norm=tc.clip_norm)
        new_state = {"params": params, "opt": opt, "step": state["step"] + 1}
        out_metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        if tc.compress_grads and err is not None:
            return new_state, out_metrics, new_err
        return new_state, out_metrics

    return step


class Trainer:
    """Fault-tolerant training driver (single-controller)."""

    def __init__(self, cfg: ArchConfig, state, step_fn, loader, *,
                 ckpt: Optional[CheckpointManager] = None,
                 injector: Optional[FailureInjector] = None,
                 monitor: Optional[StragglerMonitor] = None,
                 ckpt_every: int = 50,
                 clock: Optional[Callable[[], float]] = None):
        """``clock`` is the time source for per-step durations (history
        ``dt`` and the straggler monitor). Default is wall clock; a run
        whose memory system goes through UnifiedMemory should pass the
        modeled clock — ``clock=lambda: um.clock`` — so training metrics
        are directly comparable to the serve stack's ``ServeEngine.now()``
        timings instead of mixing modeled and wall seconds."""
        self.cfg = cfg
        self.state = state
        self.step_fn = step_fn
        self.loader = loader
        self.ckpt = ckpt
        self.injector = injector
        self.monitor = monitor or StragglerMonitor()
        self.ckpt_every = ckpt_every
        self.clock = clock or time.perf_counter
        self.history: list = []
        self.restarts = 0

    def run(self, num_steps: int) -> Dict[str, Any]:
        done = 0
        while done < num_steps:
            try:
                step_idx, batch = next(self.loader)
                t0 = self.clock()
                if self.injector is not None:
                    self.injector.maybe_fail(step_idx)
                self.state, metrics = self.step_fn(self.state, batch)
                loss = float(metrics["loss"])
                dt = self.clock() - t0
                self.monitor.record("worker0", dt)
                self.history.append({"step": step_idx, "loss": loss, "dt": dt})
                done += 1
                if self.ckpt is not None and (step_idx + 1) % self.ckpt_every == 0:
                    self.ckpt.save(step_idx + 1, self.state)
            except SimulatedFailure:
                # restore-and-replay: deterministic pipeline guarantees the
                # same batches stream again from the restored step
                self.restarts += 1
                assert self.ckpt is not None, "failure without checkpointing"
                self.ckpt.wait()
                step, self.state = self.ckpt.restore(self.state)
                self.loader.seek(step)
        if self.ckpt is not None:
            self.ckpt.wait()
        return {"history": self.history, "restarts": self.restarts,
                "stragglers": self.monitor.stragglers()}
