"""Operations and bytes of a sparse-expert decoder with sliding-window and
full attention layers (``bench/model_moe.py``'s configurations), counted
from shapes as ``bench/flops.py`` counts a dense one: the model's own
arithmetic, a multiply-add 2 FLOPs, no padding or masked work.

A token multiplies through, in each layer, the attention projections, the
router and its ``num_experts_per_tok`` experts; it attends
``min(context, sliding_window)`` keys in a sliding layer and ``context``
keys in a full one.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

from bench import flops

Cfg = Dict[str, Any]


def layer_types(cfg: Cfg):
    """The published layer types of the layers this configuration runs."""
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def keys(cfg: Cfg, layer_type: str, context: int) -> int:
    """Keys one query attends in a layer of ``layer_type``."""
    if layer_type == "sliding_attention":
        return min(context, cfg["sliding_window"])
    return context


def _attention_params(cfg: Cfg) -> int:
    d = cfg["hidden_size"]
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return d * hq * hd * 2 + d * hk * hd * 2  # q and o; k and v


def expert_params(cfg: Cfg) -> int:
    """One SwiGLU expert's weights."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_matmul_params(cfg: Cfg) -> int:
    """Weights one token multiplies through in one layer: attention, the
    router, and its experts."""
    return (_attention_params(cfg) + cfg["hidden_size"] * cfg["num_experts"]
            + cfg["num_experts_per_tok"] * expert_params(cfg))


def param_count(cfg: Cfg) -> int:
    """Every weight: embedding, head, and per layer attention, two norms,
    the router and all experts; the final norm."""
    d, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    layer = (_attention_params(cfg) + 2 * d + d * cfg["num_experts"]
             + cfg["num_experts"] * expert_params(cfg))
    head = 0 if cfg["tie_word_embeddings"] else V * d
    return V * d + head + L * layer + d


def attention_flops(cfg: Cfg, context: int) -> int:
    """One query position against ``context`` keys, all layers (QK and PV),
    each layer's window applied."""
    per_key = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    return per_key * sum(keys(cfg, t, context) for t in layer_types(cfg))


def _keys_sum(cfg: Cfg, layer_type: str, a: int, b: int) -> int:
    """Sum of ``keys`` over contexts a..b (inclusive)."""
    if layer_type != "sliding_attention":
        return (a + b) * (b - a + 1) // 2
    w = cfg["sliding_window"]
    lo_end = min(b, w)  # contexts a..lo_end attend all their keys
    below = (a + lo_end) * (lo_end - a + 1) // 2 if a <= lo_end else 0
    return below + w * max(0, b - max(a - 1, w))


def prefill_flops(cfg: Cfg, start: int, end: int, logits: bool) -> int:
    """Prompt positions [start, end) of one sequence, causal and windowed;
    ``logits`` when the chunk ends the prompt and its last position's
    logits count."""
    n = end - start
    dense = 2 * cfg["num_hidden_layers"] * layer_matmul_params(cfg) * n
    per_key = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    attn = per_key * sum(_keys_sum(cfg, t, start + 1, end)
                         for t in layer_types(cfg))
    return dense + attn + (flops.head_flops(cfg) if logits else 0)


def decode_flops(cfg: Cfg, contexts: Sequence[int]) -> int:
    """One decode step: each sequence's new token against its context."""
    per = (2 * cfg["num_hidden_layers"] * layer_matmul_params(cfg)
           + flops.head_flops(cfg))
    return sum(per + attention_flops(cfg, c) for c in contexts)


def expert_ffn_cost(cfg: Cfg, rows: int, experts_hit: int, itemsize: int):
    """(FLOPs, bytes) of the expert matmuls for ``rows`` (token, expert)
    rows that reached ``experts_hit`` expert instances (summed over layer
    calls): each row through one expert's three projections; the weights
    of every expert hit read once, and each row's input read and output
    written once, in the model's dtype."""
    d = cfg["hidden_size"]
    f = 2 * expert_params(cfg) * rows
    b = (experts_hit * expert_params(cfg) + 2 * rows * d) * itemsize
    return f, b


def windowed_attention_cost(cfg: Cfg, contexts: Sequence[int],
                            q_itemsize: int, kv_itemsize: int):
    """(FLOPs, bytes) of one decode step's paged attention over all layers:
    each sequence's new token against its keys, windowed in sliding layers
    (``bench/flops.paged_attention_cost`` per layer)."""
    f = b = 0
    for t in layer_types(cfg):
        lf, lb = flops.paged_attention_cost(
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], [keys(cfg, t, c) for c in contexts],
            q_itemsize, kv_itemsize)
        f, b = f + lf, b + lb
    return f, b
