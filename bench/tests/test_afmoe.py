"""The afmoe block (Trinity) at a CPU size, `data/tiny-afmoe.json`: 2 dense
and 4 expert layers, windowed and full layers 3:1, window 8, 8 routed
experts held of a 32-wide router, vocabulary 512.

Prefill (chunked, with a prefix hit) and then decode through
`PagedServingEngine` must agree with the family's reference
(`bench/reference/afmoe.py`) past the window, by the comparison that
decides a run's `correct`; the fp8 control must not, and neither may the
reference with its window mask, or its NoPE full layers, taken out. The
limits are the file's own, set between the program's highest reading over
ten seeds on the CPU and the control's lowest: `wide_gap_share` (wide gap
0.01) at most 0.049 against at least 0.243, `mean_gap` at most 0.00171
against at least 0.0124. The widest gap does not part them at this size
(0.093 against 0.113), so it is not compared.
"""
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check
from bench import weights as W
from bench.drivers.afmoe import program_config, program_params
from bench.run import BENCH, Run, Span, load_module
from bench.reference import afmoe

DATA = Path(__file__).resolve().parent / "data"


def _cfg():
    return json.loads((DATA / "tiny-afmoe.json").read_text())


def serve(cfg, seed, n_req=6, gen=24):
    from repro.models import build_model
    from repro.serve import PagedServingEngine

    pcfg = program_config(cfg)
    spec = afmoe.leaves(cfg)
    params = program_params(spec, W.make_params(spec, seed), build_model(pcfg),
                            pcfg.n_dense_layers)
    eng = PagedServingEngine(pcfg, block_size=4, num_blocks=256, params=params,
                             max_in_flight=4)
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg["vocab_size"], 42).tolist()
    prompts = []
    for i in range(n_req):
        body = rng.integers(0, cfg["vocab_size"], int(rng.integers(8, 70))).tolist()
        prompts.append(shared + body if i % 2 else body)
    rids = [eng.submit(p, max_new_tokens=gen) for p in prompts]
    stats = eng.run()
    assert stats["completed"] == n_req
    recs = [SimpleNamespace(rid=r, prompt=p, tokens=eng.request(r).generated)
            for r, p in zip(rids, prompts)]
    return recs, stats, eng


@pytest.fixture(scope="module")
def served():
    cfg = _cfg()
    recs, stats, _ = serve(cfg, 2**32 + 11)
    return cfg, 2**32 + 11, recs, stats


def _judged(cfg, seed, recs, **kw):
    out = check.compare(afmoe, cfg, seed, recs, pad_to=512, **kw)
    return out, check.judge(cfg, out, 0)[0]


def test_served_tokens_match_the_reference_past_the_window(served):
    cfg, seed, recs, stats = served
    assert stats["prefix_hits"] > 0 and stats["cow_forks"] > 0
    # every request runs past the window of 8
    assert min(len(r.prompt) + len(r.tokens) for r in recs) > 3 * cfg["sliding_window"]
    out, ok = _judged(cfg, seed, recs, control=True)
    assert ok, out
    for key, limit in cfg["check"]["limits"].items():
        assert out[key] <= limit < out["control_" + key]


@pytest.mark.parametrize("removed", ["window mask", "NoPE"])
def test_the_reference_without_the_window_or_nope_fails(served, monkeypatch, removed):
    cfg, seed, recs, _ = served
    kinds = afmoe.layer_kinds(cfg)
    if removed == "window mask":
        changed = [(0, rope) for _, rope in kinds]       # sliding layers see all
    else:
        changed = [(w, True) for w, _ in kinds]         # full layers get RoPE
    assert changed != kinds
    monkeypatch.setattr(afmoe, "layer_kinds", lambda cfg: changed)
    out, ok = _judged(cfg, seed, recs)
    assert not ok, out


def test_expert_shares_sum_to_the_uncut_layer():
    """Four chips' shares of a 32-expert layer, 8 experts each, with the
    shared expert (which every chip computes alike) counted once, add up
    to the layer that holds all 32."""
    from repro.models import build_model
    from repro.models.moe import moe_dense, shared_expert

    pcfg = program_config(_cfg()).replace(dtype="float32", param_dtype="float32",
                                          experts_held=32)
    p = build_model(pcfg).init(jax.random.PRNGKey(3))["layers"]["moe"]
    p = jax.tree.map(lambda a: a[0], p)
    p["expert_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (32,))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 7, pcfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole, _ = moe_dense(p, x, pcfg)
        parts = []
        for s in range(4):
            share = dict(p, **{k: p[k][8 * s:8 * s + 8]
                               for k in ("w_gate", "w_up", "w_down")})
            y, _ = moe_dense(share, x, pcfg.replace(experts_held=8), first=8 * s)
            parts.append(y)
        shared = shared_expert(p["shared"], x)
    total = sum(parts) - 3 * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=1e-5)
    # each share is a real part: none is the whole, none is only the shared
    for y in parts:
        assert float(jnp.abs(y - shared).max()) > 1e-3
        assert float(jnp.abs(y - whole).max()) > 1e-3


def test_the_bias_moves_the_choice_and_not_the_weights():
    from repro.models.moe import router_topk

    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 32))
    scores = jax.nn.sigmoid(x @ w)
    zero = jnp.zeros(32)
    gates, experts, _ = router_topk(x, w, 4, bias=zero, scale=2.826)
    # weights: the chosen experts' scores, normalised and scaled
    top = jnp.take_along_axis(scores, experts, axis=-1)
    np.testing.assert_allclose(gates, 2.826 * top / top.sum(-1, keepdims=True),
                               rtol=1e-6)
    # a bias on one expert makes it chosen by every token, at its own score
    bias = zero.at[5].set(10.0)
    g2, e2, _ = router_topk(x, w, 4, bias=bias, scale=2.826)
    assert bool((e2 == 5).any(-1).all())
    top2 = jnp.take_along_axis(scores, e2, axis=-1)
    np.testing.assert_allclose(g2, 2.826 * top2 / top2.sum(-1, keepdims=True),
                               rtol=1e-6)
    assert not np.allclose(np.sort(g2, -1), np.sort(gates, -1))
    # the same shift for every expert changes nothing
    g3, e3, _ = router_topk(x, w, 4, bias=zero + 0.3, scale=2.826)
    np.testing.assert_array_equal(e3, experts)
    np.testing.assert_allclose(g3, gates, rtol=1e-6)


def test_decode_rounds_carry_the_kv_bytes_they_gather():
    """On a CPU engine run, every `decode_round` span carries the window's
    blocks and the K+V bytes reckoned from the layer kinds, the round's
    width and the table width; `decode_kv_read_mb` reads their mean."""
    from repro.obs import trace as obs_trace
    from repro.serve.engine import decode_kv_bytes

    cfg = _cfg()
    obs_trace.reset()        # an empty ring
    _, _, eng = serve(cfg, 7, n_req=3, gen=12)
    events = [e for e in obs_trace.get_tracer().events
              if e.get("name") == "decode_round" and e.get("ph") == "X"]
    assert events
    pcfg = eng.cfg
    # 6 layers: 5 windowed (window 8: 3 blocks of 4), 1 full
    per_block = 2 * 4 * pcfg.kv_heads * pcfg.resolved_head_dim * 2
    for e in events:
        a = e["args"]
        tw = a["table_width"]
        assert a["window_blocks"] == min(3, tw)
        want = eng.round_width * (5 * min(3, tw) + tw) * per_block
        assert a["kv_bytes"] == want == decode_kv_bytes(pcfg, eng.round_width, tw, 4)
    reader = load_module(BENCH / "metrics" / "decode_kv_read_mb.py",
                         "bench_metric_decode_kv_read_mb")
    spans = [Span(e["name"], 0.0, 1.0, e["args"]) for e in events]
    run = Run(cfg, None, {}, None, spans)
    assert reader.read(run) == pytest.approx(
        np.mean([e["args"]["kv_bytes"] for e in events]) / 1e6)
    assert reader.read(Run(cfg, None, {}, None, [Span("decode_round", 0, 1, {})])) is None


def test_kv_bytes_at_the_cells_sizes():
    """granite-moe-1b-a400m's round reads 24 full-width layers; trinity-mini's
    cut (12 windowed, 4 full) at table width 512 reads
    (12 x 129 + 4 x 512) / (16 x 512) of a full-width read."""
    from repro.configs import get_config
    from repro.serve.engine import decode_kv_bytes

    granite = get_config("granite-moe-1b-a400m").replace(dtype="bfloat16")
    assert decode_kv_bytes(granite, 20, 192, 16) == 20 * 24 * 192 * 16 * 2 * 512 * 2
    trinity = program_config(json.loads((BENCH / "configs" / "trinity-mini.json")
                                        .read_text()))
    full = trinity.replace(sliding_window=0)
    got = decode_kv_bytes(trinity, 20, 512, 16) / decode_kv_bytes(full, 20, 512, 16)
    assert got == pytest.approx((12 * 129 + 4 * 512) / (16 * 512))
    assert got == pytest.approx(0.44, abs=0.005)


@pytest.mark.parametrize("kind", [None, "one:32"])
def test_a_run_of_the_cell_on_the_afmoe_file(tiny_checkout, run_tiny, monkeypatch,
                                            kind):
    """`bench/run.py`'s run of a cell on the afmoe file at the CPU size: the
    afmoe driver, the family's reference and the check together, correct;
    with about one wrong token a request planted, not."""
    from bench import faults

    if kind is not None:
        faults.plant(kind, monkeypatch.setattr)
    out = run_tiny(tiny_checkout("tiny-afmoe"), 2**32 + 3)
    assert out["correct"] is (kind is None), out["checks"]


def test_the_sample_takes_requests_still_decoding_at_the_close():
    """Outputs that outlast the window: the correctness sample holds the
    requests still decoding when it closes, with the tokens served by then,
    and none of them counts as failed."""
    import time

    from bench import traffic as T
    from bench.drivers import afmoe as A
    from test_driver import FakeEngine, V

    class Engine(FakeEngine):
        def request(self, rid):
            return self.live[rid]

    endless = {"median": 10**6, "sigma": 0.1, "lo": 10**6, "hi": 10**6}
    mix = dict(T.load_mix("offline-decode"), context_cap=10**7,
               arrivals={"process": "backlog", "requests": 50, "queue_factor": 2},
               classes=[{"output_len": endless,
                         "user_len": {"median": 8, "sigma": 0.3, "lo": 4, "hi": 16}}])
    drv = A.ServeDriver({"vocab_size": V}, None, mix, 3, 0.3)

    def build():
        drv.engine = Engine(drv._on_token, drv._on_finish)
        drv.traffic = T.build(mix, 3, V, drv._horizon())

    drv.build = build
    drv.warm_up = lambda: None
    drv.run(time.perf_counter(), SimpleNamespace(between=lambda a, b: []))
    sample = drv.finished()
    assert sample and all(r.state == "open" for r in sample)
    for r in sample:
        assert r.tokens == drv.engine.live[r.rid].generated
    assert drv.counts()[1] == 0


def test_trinity_minis_limits_pass_its_flips_and_fail_a_wrong_token_a_request():
    """trinity-mini's file as calibrated on a TPU v5e: bfloat16 flips (10%
    of positions, 3% over the wide gap, the widest 0.55) pass; about one
    wrong token a request among some 3300 (gaps of 5.71, the least the
    planted fault read) stays under the share and fails the cap."""
    cfg = json.loads((BENCH / "configs" / "trinity-mini.json").read_text())
    wide = cfg["check"]["wide_gap"]

    def judged(g):
        return check.judge(cfg, dict(check.numbers(g, wide), tokens_compared=g.size), 0)

    g = np.zeros(3300)
    g[::10] = wide / 2
    g[::35] = 0.3
    g[[10, 400]] = 0.55
    assert judged(g)[0]
    g[::250] = 5.71
    ok, checks = judged(g)
    assert checks["wide_gap_share"]["value"] <= checks["wide_gap_share"]["limit"]
    assert not ok and checks["logit_gap"]["value"] > checks["logit_gap"]["limit"]
