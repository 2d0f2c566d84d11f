"""FLOP and byte counts against numbers reckoned by hand, and against the
numbers the counts gave before each family gave its own sizes."""
import json
from pathlib import Path

import pytest

from bench import work
from bench.run import load_family

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
G2B_CFG = json.loads((CONFIGS / "granite-3-2b.json").read_text())
MOE_CFG = json.loads((CONFIGS / "granite-moe-1b-a400m.json").read_text())
G2B = load_family(G2B_CFG).sizes(G2B_CFG)
MOE = load_family(MOE_CFG).sizes(MOE_CFG)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# granite-3-2b by hand: attention 2048*32*64 (q) + 2*2048*8*64 (k, v)
# + 32*64*2048 (o) = 10,485,760; SwiGLU 3*2048*8192 = 50,331,648; two norms
G2B_LAYER = 10_485_760 + 50_331_648 + 2 * 2048
G2B_PARAMS = 40 * G2B_LAYER + 49155 * 2048 + 2048


def test_granite_3_2b_weights_and_kv():
    assert G2B.layers == (G2B.layers[0],) * 40
    assert G2B.layers[0].params == G2B.layers[0].active == G2B_LAYER
    assert work.weight_bytes(G2B) == 2 * G2B_PARAMS
    assert work.weight_bytes(G2B) == pytest.approx(5.07e9, rel=1e-3)
    assert work.kv_bytes_per_token(G2B) == 80 * 1024   # 40 x 2 x 8 x 64 x 2 B


def test_granite_moe_weights_and_kv():
    # 24 layers of 32 experts x 3 x 1024 x 512, router 1024 x 32, attention
    attn = 1024 * 16 * 64 + 2 * 1024 * 8 * 64 + 16 * 64 * 1024
    layer = attn + 32 * 3 * 1024 * 512 + 1024 * 32 + 2 * 1024
    assert MOE.layers == (MOE.layers[0],) * 24
    assert MOE.layers[0].params == layer
    assert work.weight_bytes(MOE) == 2 * (24 * layer + 49155 * 1024 + 1024)
    assert work.weight_bytes(MOE) == pytest.approx(2.67e9, rel=0.01)
    assert work.kv_bytes_per_token(MOE) == 48 * 1024   # 24 x 2 x 8 x 64 x 2 B
    # a token multiplies through 8 of the 32 experts
    assert MOE.layers[0].active == layer - 24 * 3 * 1024 * 512


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


# Every count of both configurations as the counts read them before the
# family gave the sizes (commit e02db59): the metrics that read them must not
# move.
PARENT = {
    "granite-3-2b": {
        'weight_bytes': 5067063296,
        'kv_bytes_per_token': 81920,
        'step_weight_bytes 1': 5067063296.0,
        'step_weight_bytes 7': 5067063296.0,
        'step_weight_bytes 20': 5067063296.0,
        'step_weight_bytes 52': 5067063296.0,
        'decode_flops [1]': 5067386880.0,
        'decode_bytes [1]': 5067227136.0,
        'decode_flops [1000, 3000]': 11444838400.0,
        'decode_bytes [1000, 3000]': 5394907136.0,
        'decode_flops [17, 255, 2048, 3071, 5, 900, 64]': 37553459200.0,
        'decode_bytes [17, 255, 2048, 3071, 5, 900, 64]': 5588647936.0,
        'prefill_flops 0 32': 156077404160.0,
        'prefill_bytes 0 32': 5072306176.0,
        'prefill_flops 100 32': 157125980160.0,
        'prefill_bytes 100 32': 5080498176.0,
        'prefill_flops 2040 8': 44486635520.0,
        'prefill_bytes 2040 8': 5235490816.0,
        'prefill_flops 0 1': 5067386880.0,
        'prefill_bytes 0 1': 5067227136.0,
    },
    "granite-moe-1b-a400m": {
        'weight_bytes': 2669256704,
        'kv_bytes_per_token': 49152,
        'step_weight_bytes 1': 857317376.0,
        'step_weight_bytes 7': 2346770432.0,
        'step_weight_bytes 20': 2661595312.4938965,
        'step_weight_bytes 52': 2669255934.394639,
        'decode_flops [1]': 857413632.0,
        'decode_bytes [1]': 857415680.0,
        'decode_flops [1000, 3000]': 2107846656.0,
        'decode_bytes [1000, 3000]': 1507008512.0,
        'decode_flops [17, 255, 2048, 3071, 5, 900, 64]': 6626420736.0,
        'decode_bytes [17, 255, 2048, 3071, 5, 900, 64]': 2659721216.0,
        'prefill_flops 0 32': 24365242368.0,
        'prefill_bytes 0 32': 2672159747.0656586,
        'prefill_flops 100 32': 24679815168.0,
        'prefill_bytes 100 32': 2677074947.0656586,
        'prefill_flops 2040 8': 7761696768.0,
        'prefill_bytes 2040 8': 2528448512.0,
        'prefill_flops 0 1': 857413632.0,
        'prefill_bytes 0 1': 857415680.0,
    },
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_every_count_is_what_it_was(name):
    s = {"granite-3-2b": G2B, "granite-moe-1b-a400m": MOE}[name]
    got = {"weight_bytes": work.weight_bytes(s),
           "kv_bytes_per_token": work.kv_bytes_per_token(s)}
    for t in (1, 7, 20, 52):
        got[f"step_weight_bytes {t}"] = work.step_weight_bytes(s, t)
    for ctx in ([1], [1000, 3000], [17, 255, 2048, 3071, 5, 900, 64]):
        got[f"decode_flops {ctx}"] = work.decode_flops(s, ctx)
        got[f"decode_bytes {ctx}"] = work.decode_bytes(s, ctx)
    for start, n in ((0, 32), (100, 32), (2040, 8), (0, 1)):
        got[f"prefill_flops {start} {n}"] = work.prefill_flops(s, start, n)
        got[f"prefill_bytes {start} {n}"] = work.prefill_bytes(s, start, n)
    assert got == PARENT[name]
    assert [type(v) for v in got.values()] == [type(v) for v in PARENT[name].values()]


def test_a_window_caps_the_keys_read():
    """A layer that attends the last `window` keys reads no more of them,
    in decode and in a prefill chunk, and does no more work on them."""
    full = G2B.layers[0]
    win = full._replace(window=512)
    s = G2B._replace(layers=(win,) * 40)
    kv = 80 * 1024
    assert work.decode_bytes(s, [100, 3000]) == (
        work.weight_bytes(G2B) + kv * (100 + 512) + kv * 2)
    assert work.decode_flops(s, [3000]) == work.decode_flops(G2B, [512])
    # a chunk of 32 at 1000: the first query reads 512 keys, each later one
    # a newer key besides
    assert work.prefill_bytes(s, 1000, 32) == (
        work.weight_bytes(G2B) + kv * (512 + 31) + kv * 32)
    assert work.prefill_bytes(s, 0, 32) == work.prefill_bytes(G2B, 0, 32)
