"""The work a granite step needs: floating-point operations and HBM bytes,
from a configuration file and the lengths a round served.

These count what the step has to do, whatever implements it: every bf16
weight read once per program call, the keys and values of the *live*
tokens each row attends to read once and the new row written once, and
two operations per multiply-add. Padding, gathers of unused blocks, and
experts computed for tokens not routed to them are not work: removing them
raises the shares these numbers feed.
"""
from __future__ import annotations

from typing import Iterable

from bench.weights import dims

BF16 = 2


def layer_params(cfg: dict) -> int:
    """Parameters of one layer (all experts)."""
    m = dims(cfg)
    d, h, kh, hd = m["d"], m["h"], m["kh"], m["hd"]
    attn = d * h * hd + 2 * d * kh * hd + h * hd * d + d
    if m["E"]:
        return attn + d + d * m["E"] + m["E"] * 3 * d * m["f"]
    return attn + d + 3 * d * m["f"]


def active_layer_params(cfg: dict) -> int:
    """Parameters one token multiplies through in one layer: the routed
    experts only."""
    m = dims(cfg)
    if not m["E"]:
        return layer_params(cfg)
    return layer_params(cfg) - (m["E"] - m["k"]) * 3 * m["d"] * m["f"]


def weight_bytes(cfg: dict) -> int:
    """All served weights in bf16: layers, tied embedding, final norm."""
    m = dims(cfg)
    return BF16 * (m["L"] * layer_params(cfg) + m["V"] * m["d"] + m["d"])


def kv_bytes_per_token(cfg: dict) -> int:
    m = dims(cfg)
    return m["L"] * 2 * m["kh"] * m["hd"] * BF16


def expected_experts(cfg: dict, tokens: int) -> float:
    """Experts a batch of `tokens` routes to, if routing were uniform."""
    m = dims(cfg)
    if not m["E"]:
        return 0.0
    return m["E"] * (1.0 - (1.0 - m["k"] / m["E"]) ** tokens)


def step_weight_bytes(cfg: dict, tokens: int) -> float:
    """Weights one program call must read for `tokens` tokens: everything
    but the experts no token is routed to."""
    m = dims(cfg)
    if not m["E"]:
        return float(weight_bytes(cfg))
    unused = m["E"] - expected_experts(cfg, tokens)
    return weight_bytes(cfg) - BF16 * m["L"] * unused * 3 * m["d"] * m["f"]


def _attn_flops(cfg: dict, keys: int) -> int:
    """Scores and weighted values of one query over `keys` keys, all layers."""
    m = dims(cfg)
    return 4 * m["L"] * m["h"] * m["hd"] * keys


def decode_flops(cfg: dict, ctx_lens: Iterable[int]) -> float:
    """One decode round: each row's token through every layer and the head;
    `ctx_lens` are the positions each row attends (its new token included)."""
    m = dims(cfg)
    per_token = 2 * (m["L"] * active_layer_params(cfg) + m["V"] * m["d"])
    return float(sum(per_token + _attn_flops(cfg, n) for n in ctx_lens))


def decode_bytes(cfg: dict, ctx_lens: Iterable[int]) -> float:
    lens = list(ctx_lens)
    kv = kv_bytes_per_token(cfg)
    return step_weight_bytes(cfg, len(lens)) + kv * (sum(lens) + len(lens))


def prefill_flops(cfg: dict, start: int, n: int) -> float:
    """One prefill chunk of `n` tokens at positions start..start+n-1, with
    the logits of its last token."""
    m = dims(cfg)
    attn = sum(_attn_flops(cfg, start + i + 1) for i in range(n))
    return float(2 * n * m["L"] * active_layer_params(cfg) + attn
                 + 2 * m["V"] * m["d"])


def prefill_bytes(cfg: dict, start: int, n: int) -> float:
    kv = kv_bytes_per_token(cfg)
    return step_weight_bytes(cfg, n) + kv * (start + n) + kv * n


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
