"""FLOP and byte counts against numbers reckoned by hand."""
import json
from pathlib import Path

import pytest

from bench import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
G2B = json.loads((CONFIGS / "granite-3-2b.json").read_text())
MOE = json.loads((CONFIGS / "granite-moe-1b-a400m.json").read_text())
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# granite-3-2b by hand: attention 2048*32*64 (q) + 2*2048*8*64 (k, v)
# + 32*64*2048 (o) = 10,485,760; SwiGLU 3*2048*8192 = 50,331,648; two norms
G2B_LAYER = 10_485_760 + 50_331_648 + 2 * 2048
G2B_PARAMS = 40 * G2B_LAYER + 49155 * 2048 + 2048


def test_granite_3_2b_weights_and_kv():
    assert work.layer_params(G2B) == G2B_LAYER
    assert work.weight_bytes(G2B) == 2 * G2B_PARAMS
    assert work.weight_bytes(G2B) == pytest.approx(5.07e9, rel=1e-3)
    assert work.kv_bytes_per_token(G2B) == 80 * 1024   # 40 x 2 x 8 x 64 x 2 B


def test_granite_moe_weights_and_kv():
    # 24 layers of 32 experts x 3 x 1024 x 512, router 1024 x 32, attention
    attn = 1024 * 16 * 64 + 2 * 1024 * 8 * 64 + 16 * 64 * 1024
    layer = attn + 32 * 3 * 1024 * 512 + 1024 * 32 + 2 * 1024
    assert work.layer_params(MOE) == layer
    assert work.weight_bytes(MOE) == 2 * (24 * layer + 49155 * 1024 + 1024)
    assert work.weight_bytes(MOE) == pytest.approx(2.67e9, rel=0.01)
    assert work.kv_bytes_per_token(MOE) == 48 * 1024   # 24 x 2 x 8 x 64 x 2 B
    # a token multiplies through 8 of the 32 experts
    assert work.active_layer_params(MOE) == layer - 24 * 3 * 1024 * 512


def test_decode_round_by_hand():
    ctx = [1000, 3000]
    per_token = 2 * (40 * G2B_LAYER + 49155 * 2048)
    attn = 4 * 40 * 32 * 64 * (1000 + 3000)
    assert work.decode_flops(G2B, ctx) == 2 * per_token + attn
    assert work.decode_bytes(G2B, ctx) == 2 * G2B_PARAMS + 80 * 1024 * (4000 + 2)
    # two rows of granite-3-2b decode are bound by memory: ~6.2 ms of weights
    t = work.least_seconds(work.decode_flops(G2B, ctx),
                           work.decode_bytes(G2B, ctx), PEAKS)
    assert t == pytest.approx((2 * G2B_PARAMS + 80 * 1024 * 4002) / 819e9)
    assert 6.1e-3 < t < 6.7e-3


def test_prefill_chunk_by_hand():
    flops = work.prefill_flops(G2B, start=100, n=32)
    attn = 4 * 40 * 32 * 64 * sum(100 + i + 1 for i in range(32))
    assert flops == 2 * 32 * 40 * G2B_LAYER + attn + 2 * 49155 * 2048
    assert work.prefill_bytes(G2B, 100, 32) == 2 * G2B_PARAMS + 80 * 1024 * (132 + 32)


def test_moe_reads_only_experts_that_tokens_reach():
    full = work.weight_bytes(MOE)
    one = work.step_weight_bytes(MOE, 1)
    expert = 2 * 24 * 3 * 1024 * 512
    assert one == pytest.approx(full - 24 * expert)     # 8 of 32 experts read
    assert work.step_weight_bytes(MOE, 20) == pytest.approx(full, rel=0.01)
    assert work.step_weight_bytes(G2B, 1) == work.weight_bytes(G2B)
