"""Step-function factories for the dry-run: (fn, args, in_shardings) per cell.

Artifacts per run-shape kind (see DESIGN.md §Roofline for why two train
artifacts exist — XLA's HloCostAnalysis visits while bodies once, so FLOPs/
collectives are read from python-unrolled lowerings while the scan+remat
full step proves memory):

  train   -> 'train_memory' (scan+remat, full global batch, whole update)
             'micro_grads'  (one microbatch fwd+bwd, unrolled, remat)
             'opt_update'   (grad application)
  prefill -> 'prefill' (unrolled, block-causal attention)
  decode  -> 'decode'  (unrolled serve_step: 1 token, dense cache)
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, RunShape
from repro.launch.mesh import dp_axes, dp_size, tp_size
from repro.launch.sharding import (
    batch_spec,
    cache_specs_tree,
    make_run_policy,
    opt_specs,
    param_specs,
    stacked_param_specs,
    stacked_params_sds,
)
from repro.models import loss_fn, sync_replica_grads, grad_mask
from repro.models.cache import cache_specs
from repro.models.transformer import decode_step, forward, init_params_specs, prefill
from repro.optim import adamw_update
from repro.optim.schedule import warmup_cosine
from repro.train.trainer import split_microbatches


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _token_sds(cfg: ArchConfig, B: int, S: int):
    if cfg.input_kind == "embeddings":
        return jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
    return jax.ShapeDtypeStruct((B, S), jnp.int32)


def make_artifacts(cfg: ArchConfig, shape: RunShape, mesh,
                   *, dtype=jnp.bfloat16, attn_block: int = 4096,
                   sequence_parallel: bool = False,
                   mode: str = "full",
                   extra_policy: Optional[Dict[str, Any]] = None
                   ) -> Dict[str, Tuple[Callable, tuple, Any]]:
    """Returns {artifact: (fn, args_SDS, in_shardings)}.

    mode='full'  -> cost probes (unrolled) + memory artifacts (scan).
    mode='proof' -> memory/scan artifacts only (fast compile; used for the
                    multi-pod coherence pass).
    """
    tp = tp_size(mesh)
    dsz = dp_size(mesh)
    B, S = shape.global_batch, shape.seq_len
    pspec = param_specs(init_params_specs(cfg, dtype=dtype, tp=tp), mesh)
    params_sds = init_params_specs(cfg, dtype=dtype, tp=tp)

    blk = min(attn_block, S)
    pol_kw = dict(remat=False,
                  attn_q_block=blk if S > attn_block else 0,
                  attn_kv_block=blk if S > attn_block else 0,
                  sequence_parallel=sequence_parallel)
    if extra_policy:
        pol_kw.update(extra_policy)
    policy = make_run_policy(mesh, **pol_kw)

    out: Dict[str, Tuple[Callable, tuple, Any]] = {}

    if shape.kind == "train":
        micro = max(dsz, B // shape.grad_accum)
        micro = min(micro, B)
        accum = B // micro
        tok = _token_sds(cfg, micro, S)
        lab = jax.ShapeDtypeStruct((micro, S), jnp.int32)
        bspec = {"tokens": batch_spec(mesh, ndim=tok.ndim, batch_size=micro),
                 "labels": batch_spec(mesh, ndim=2, batch_size=micro)}

        def micro_grads(params, batch):
            pol = make_run_policy(mesh, remat=True, **{k: v for k, v in pol_kw.items()
                                                       if k != "remat"})
            (loss, _), grads = jax.value_and_grad(
                lambda p: loss_fn(cfg, p, batch, pol), has_aux=True)(params)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            return loss, grads

        grads_sds = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params_sds)
        ospec = opt_specs(pspec, params_sds, mesh)
        gspec = ospec["m"]  # ZeRO grad sharding

        if mode == "full":
            out["micro_grads"] = (
                micro_grads,
                (params_sds, {"tokens": tok, "labels": lab}),
                (_named(mesh, pspec), _named(mesh, bspec)),
            )

        def opt_update(state, grads):
            lr = warmup_cosine(3e-4, 100, 10_000)(state["step"])
            grads = sync_replica_grads(cfg, grads, tp)
            m = grad_mask(cfg, state["params"], tp)
            grads = jax.tree.map(lambda g, mm: g * mm.astype(g.dtype), grads, m)
            p, o, gn = adamw_update(grads, state["opt"], state["params"], lr=lr)
            return {"params": p, "opt": o, "step": state["step"] + 1}

        opt_sds = {
            "m": grads_sds, "v": grads_sds,
            "master": grads_sds,
            "count": jax.ShapeDtypeStruct((), jnp.int32),
        }
        state_sds = {"params": params_sds, "opt": opt_sds,
                     "step": jax.ShapeDtypeStruct((), jnp.int32)}
        state_spec = {"params": pspec, "opt": ospec, "step": P()}
        if mode == "full":
            out["opt_update"] = (
                opt_update,
                (state_sds, grads_sds),
                (_named(mesh, state_spec), _named(mesh, gspec)),
            )

        tok_full = _token_sds(cfg, B, S)
        lab_full = jax.ShapeDtypeStruct((B, S), jnp.int32)
        bspec_full = {"tokens": batch_spec(mesh, ndim=tok_full.ndim, batch_size=B),
                      "labels": batch_spec(mesh, ndim=2, batch_size=B)}

        # memory artifact: stacked-layer state (scan-bwd accumulates into
        # param-shaped buffers; ZeRO shards get an extra L-dim cut)
        homogeneous = not cfg.layer_pattern

        def train_memory(state, batch):
            pol = make_run_policy(mesh, scan_layers=homogeneous, remat=True,
                                  **{k: v for k, v in pol_kw.items() if k != "remat"})

            def one_micro(gacc, mb):
                (_, _), g = jax.value_and_grad(
                    lambda p: loss_fn(cfg, p, mb, pol), has_aux=True)(state["params"])
                gacc = jax.tree.map(lambda a, gg: a + gg.astype(jnp.float32), gacc, g)
                gacc = jax.tree.map(
                    lambda a, sp: jax.lax.with_sharding_constraint(
                        a, NamedSharding(mesh, sp)), gacc, gspec_mem,
                )
                return gacc, None

            mb_tree = split_microbatches(batch, accum)
            gacc0 = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32),
                                 state["params"])
            grads, _ = jax.lax.scan(one_micro, gacc0, mb_tree)
            grads = jax.tree.map(lambda g: g / accum, grads)
            return opt_update(state, grads)

        if homogeneous:
            params_sds_m = stacked_params_sds(params_sds)
            pspec_m = stacked_param_specs(pspec)
        else:
            params_sds_m, pspec_m = params_sds, pspec
        ospec_m = opt_specs(pspec_m, params_sds_m, mesh)
        gspec_mem = ospec_m["m"]
        grads_sds_m = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params_sds_m)
        opt_sds_m = {"m": grads_sds_m, "v": grads_sds_m, "master": grads_sds_m,
                     "count": jax.ShapeDtypeStruct((), jnp.int32)}
        state_sds_m = {"params": params_sds_m, "opt": opt_sds_m,
                       "step": jax.ShapeDtypeStruct((), jnp.int32)}
        state_spec_m = {"params": pspec_m, "opt": ospec_m, "step": P()}
        out["train_memory"] = (
            train_memory,
            (state_sds_m, {"tokens": tok_full, "labels": lab_full}),
            (_named(mesh, state_spec_m), _named(mesh, bspec_full)),
            _named(mesh, state_spec_m),  # out: scan ys must keep shardings
        )
        out["__meta__"] = {"accum": accum, "micro": micro}

    elif shape.kind == "prefill":
        tok = _token_sds(cfg, B, S)
        bspec = batch_spec(mesh, ndim=tok.ndim, batch_size=B)

        def prefill_fn(params, tokens):
            return prefill(cfg, params, tokens, policy)

        if mode == "full":
            out["prefill"] = (  # unrolled: the cost/collective probe
                prefill_fn,
                (params_sds, tok),
                (_named(mesh, pspec), NamedSharding(mesh, bspec)),
            )

        scan_pol = make_run_policy(mesh, scan_layers=True, **pol_kw)

        def prefill_mem_fn(params, tokens):
            return prefill(cfg, params, tokens, scan_pol)

        out_sds = jax.eval_shape(prefill_mem_fn, params_sds, tok)
        stacked_out = isinstance(out_sds[1], dict)
        lspec = P(bspec[0], None,
                  "model" if cfg.vocab_size % tp == 0 else None)
        cache_out_spec = cache_specs_tree(out_sds[1], mesh, B, stacked=stacked_out)
        out["prefill_memory"] = (  # scan: the memory verdict
            prefill_mem_fn,
            (params_sds, tok),
            (_named(mesh, pspec), NamedSharding(mesh, bspec)),
            (NamedSharding(mesh, lspec), _named(mesh, cache_out_spec)),
        )

    elif shape.kind == "decode":
        tok = _token_sds(cfg, B, 1)
        pos = jax.ShapeDtypeStruct((B,), jnp.int32)
        csds = cache_specs(cfg, B, S, tp=tp, dtype=dtype,
                           kv_quant=policy.kv_cache_quant)
        cspec = cache_specs_tree(csds, mesh, B)

        def decode_fn(params, cache, tokens, pos):
            return decode_step(cfg, params, tokens, pos, cache, policy)

        if mode == "full":
            out["decode"] = (  # unrolled: cost/collective probe
                decode_fn,
                (params_sds, csds, tok, pos),
                (_named(mesh, pspec), _named(mesh, cspec),
                 NamedSharding(mesh, batch_spec(mesh, ndim=tok.ndim, batch_size=B)),
                 NamedSharding(mesh, batch_spec(mesh, ndim=1, batch_size=B))),
            )

        kinds = set(cfg.layer_kinds())
        if len(kinds) == 1 and next(iter(kinds)) in ("attention", "rwkv6"):
            # scan + stacked params/cache: the memory verdict
            params_sds_d = stacked_params_sds(params_sds)
            pspec_d = stacked_param_specs(pspec)
            L = cfg.num_layers
            csds_d = jax.tree.map(
                lambda *xs: jax.ShapeDtypeStruct((L,) + xs[0].shape, xs[0].dtype),
                *csds)
            cspec_d = cache_specs_tree(csds_d, mesh, B, stacked=True)
            scan_pol = make_run_policy(mesh, scan_layers=True, **pol_kw)

            def decode_mem_fn(params, cache, tokens, pos):
                return decode_step(cfg, params, tokens, pos, cache, scan_pol)

            lspec_d = P(batch_spec(mesh, ndim=1, batch_size=B)[0], None,
                        "model" if cfg.vocab_size % tp == 0 else None)
            out["decode_memory"] = (
                decode_mem_fn,
                (params_sds_d, csds_d, tok, pos),
                (_named(mesh, pspec_d), _named(mesh, cspec_d),
                 NamedSharding(mesh, batch_spec(mesh, ndim=tok.ndim, batch_size=B)),
                 NamedSharding(mesh, batch_spec(mesh, ndim=1, batch_size=B))),
                (NamedSharding(mesh, lspec_d), _named(mesh, cspec_d)),
            )
    else:
        raise ValueError(shape.kind)
    return out
