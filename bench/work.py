"""The work a served step needs: floating-point operations and HBM bytes,
from a block family's sizes and the lengths a round served.

These count what the step has to do, whatever implements it: every bf16
weight read once per program call, the keys and values of the *live*
tokens each row attends to read once and the new row written once, and
two operations per multiply-add. Padding, gathers of unused blocks, and
experts computed for tokens not routed to them are not work: removing them
raises the shares these numbers feed.

The sizes come from the family (`bench/reference/<family>.py`, `sizes`):
what each layer holds and multiplies per token, its keys and values, and
the head. This module keeps only the sums.
"""
from __future__ import annotations

from collections import Counter
from typing import Iterable, NamedTuple, Optional, Tuple

BF16 = 2


class Layer(NamedTuple):
    """One layer's sizes."""
    params: int                    # every parameter held
    active: int                    # those one token multiplies through
    kv_bytes: int                  # keys and values one token stores
    key_flops: int                 # operations of one query against one key
    window: Optional[int] = None   # keys attended: all, or the last `window`
    experts: int = 0               # routed experts held
    top_k: int = 0                 # of them, those a token is routed to
    expert_params: int = 0         # parameters of one routed expert


class Sizes(NamedTuple):
    """A served model's sizes, as its family gives them."""
    layers: Tuple[Layer, ...]
    head_params: int       # held outside the layers: embedding, final norm, an untied head
    unembed_params: int    # multiply-adds of one token's logits


def keys_attended(layer: Layer, ctx: int) -> int:
    """Keys one query at context length `ctx` attends in `layer`."""
    return ctx if layer.window is None else min(ctx, layer.window)


def weight_bytes(s: Sizes) -> int:
    """All served weights in bf16."""
    return BF16 * (sum(l.params for l in s.layers) + s.head_params)


def kv_bytes_per_token(s: Sizes) -> int:
    return sum(l.kv_bytes for l in s.layers)


def expected_experts(layer: Layer, tokens: int) -> float:
    """Experts a batch of `tokens` routes to in `layer`, if routing were
    uniform."""
    if not layer.experts:
        return 0.0
    return layer.experts * (1.0 - (1.0 - layer.top_k / layer.experts) ** tokens)


def step_weight_bytes(s: Sizes, tokens: int) -> float:
    """Weights one program call must read for `tokens` tokens: everything
    but the experts no token is routed to."""
    out = weight_bytes(s)
    # layers alike are counted together, as one product
    for layer, n in Counter(l for l in s.layers if l.experts).items():
        unused = layer.experts - expected_experts(layer, tokens)
        out = out - BF16 * n * unused * layer.expert_params
    return float(out)


def _attn_flops(s: Sizes, ctx: int) -> int:
    """Scores and weighted values of one query at context `ctx`, all layers."""
    return sum(l.key_flops * keys_attended(l, ctx) for l in s.layers)


def decode_flops(s: Sizes, ctx_lens: Iterable[int]) -> float:
    """One decode round: each row's token through every layer and the head;
    `ctx_lens` are the positions each row attends (its new token included)."""
    per_token = 2 * (sum(l.active for l in s.layers) + s.unembed_params)
    return float(sum(per_token + _attn_flops(s, n) for n in ctx_lens))


def decode_bytes(s: Sizes, ctx_lens: Iterable[int]) -> float:
    lens = list(ctx_lens)
    read = sum(l.kv_bytes * sum(keys_attended(l, n) for n in lens) for l in s.layers)
    return step_weight_bytes(s, len(lens)) + (read + kv_bytes_per_token(s) * len(lens))


def prefill_flops(s: Sizes, start: int, n: int) -> float:
    """One prefill chunk of `n` tokens at positions start..start+n-1, with
    the logits of its last token."""
    attn = sum(_attn_flops(s, start + i + 1) for i in range(n))
    return float(2 * n * sum(l.active for l in s.layers) + attn
                 + 2 * s.unembed_params)


def prefill_bytes(s: Sizes, start: int, n: int) -> float:
    # the chunk's queries read the keys from the oldest any of them attends
    read = sum(l.kv_bytes * (keys_attended(l, start + 1) + n - 1) for l in s.layers)
    return step_weight_bytes(s, n) + read + kv_bytes_per_token(s) * n


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
