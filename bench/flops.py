"""Operations and bytes the work needs, counted from shapes, and the chip's
peaks.

Counts are of the model's own arithmetic (a multiply-add is 2 FLOPs), not
of what a program happens to execute: recomputation, padding and masked
work do not count, so a share of a peak computed from them cannot pass
100% unless the time leaves out part of the work.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, Sequence

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, Any]:
    """The peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights one token multiplies through in one layer."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    attn = d * hq * hd * 2 + d * hk * hd * 2  # q and o; k and v
    mlp = (3 if cfg["hidden_act"] == "silu" else 2) * d * f
    return attn + mlp


def head_flops(cfg: Dict[str, Any]) -> int:
    """One position's logits."""
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def attention_flops(cfg: Dict[str, Any], context: int) -> int:
    """One query position against ``context`` keys, all layers (QK and PV)."""
    return (4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * context)


def prefill_flops(cfg: Dict[str, Any], start: int, end: int,
                  logits: bool) -> int:
    """Prompt positions [start, end) of one sequence, causal; ``logits``
    when the chunk ends the prompt and its last position's logits count."""
    n = end - start
    dense = 2 * cfg["num_hidden_layers"] * layer_matmul_params(cfg) * n
    keys = (start + 1 + end) * n // 2  # sum of p + 1 over the chunk
    return dense + attention_flops(cfg, 1) * keys + (head_flops(cfg) if logits else 0)


def decode_flops(cfg: Dict[str, Any], contexts: Sequence[int]) -> int:
    """One decode step: each sequence's new token against its context."""
    per = 2 * cfg["num_hidden_layers"] * layer_matmul_params(cfg) + head_flops(cfg)
    return sum(per + attention_flops(cfg, c) for c in contexts)


def paged_attention_cost(num_heads: int, num_kv_heads: int, head_dim: int,
                         lengths: Sequence[int], q_itemsize: int,
                         kv_itemsize: int):
    """(FLOPs, bytes) of one paged decode-attention call: every query head
    against the ``lengths[b]`` keys and values of its sequence. Bytes are
    the keys and values attended, once, in the pool's dtype, plus q read
    and the output written."""
    tokens = sum(int(n) for n in lengths)
    flops = 4 * num_heads * head_dim * tokens
    kv = 2 * num_kv_heads * head_dim * tokens * kv_itemsize
    qo = 2 * len(lengths) * num_heads * head_dim * q_itemsize
    return flops, kv + qo


def roofline_seconds(flops: float, nbytes: float, peak: Dict[str, Any]):
    """(least seconds, bound) where bound names the term that sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
