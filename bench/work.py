"""Operations and bytes a dense decoder needs, from its sizes alone.

These count the work the model requires, whatever implements it: matmul
parameters once per token, attention at each token's real context, the LM
head once per produced token.  Padding, masked slots and the redundant work
of protection are not counted.  ``m`` is a configuration's ``model`` dict.
"""
from __future__ import annotations


def _window(m: dict) -> int:
    return m.get("window", 0) if "L" in m["block_pattern"] else 0


def _ctx(m: dict, ctx: int) -> int:
    w = _window(m)
    return min(ctx, w) if w else ctx


def layer_params(m: dict) -> int:
    """Matmul parameters of one layer (biases and norms left out)."""
    D, H, KH, Dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["d_head"], m["d_ff"])
    ffn = (3 if m.get("glu", True) else 2) * D * F
    return D * (H + 2 * KH) * Dh + H * Dh * D + ffn


def head_params(m: dict) -> int:
    return m["vocab"] * m["d_model"]


def attn_flops(m: dict, ctx: int) -> float:
    """Scores and values of one token attending to ``ctx`` positions, all
    layers."""
    return 4.0 * m["n_heads"] * m["d_head"] * _ctx(m, ctx) * m["n_layers"]


def prefill_flops(m: dict, plen: int) -> float:
    """A prompt of ``plen`` real tokens, logits at its last position only."""
    mm = 2.0 * layer_params(m) * m["n_layers"] * plen
    return (mm + sum(attn_flops(m, i + 1) for i in range(plen))
            + 2.0 * head_params(m))


def decode_flops(m: dict, ctx: int) -> float:
    """One decoded token that attends to ``ctx`` positions."""
    return (2.0 * (layer_params(m) * m["n_layers"] + head_params(m))
            + attn_flops(m, ctx))


def request_decode_flops(m: dict, plen: int, n_out: int) -> float:
    """The decode steps of a request: its first token comes from prefill,
    token j >= 1 attends to plen + j positions."""
    return sum(decode_flops(m, plen + j) for j in range(1, n_out))


def weight_bytes(m: dict, dtype_bytes: int = 2) -> int:
    """Weights a decode step reads once: every layer and the LM head."""
    return dtype_bytes * (layer_params(m) * m["n_layers"] + head_params(m))


def kv_bytes(m: dict, ctx: int, dtype_bytes: int = 2) -> int:
    """Keys and values one token's step reads: ``ctx`` positions, all
    layers."""
    return (dtype_bytes * 2 * m["n_kv_heads"] * m["d_head"] * _ctx(m, ctx)
            * m["n_layers"])


def decode_bytes(m: dict, requests, steps: int) -> float:
    """Least bytes of ``steps`` decode steps that served ``requests``, a list
    of (prompt length, tokens produced): the weights once per step, plus
    each decoded token's keys and values at its real context."""
    kv = sum(kv_bytes(m, p + j) for p, n in requests for j in range(1, n))
    return float(steps) * weight_bytes(m) + kv
