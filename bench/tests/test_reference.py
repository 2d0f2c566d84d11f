"""The plain reference against the served path at a CPU size.

Prefill (chunked, through the paged cache, with a prefix hit and its
copy-on-write fork) and then decode through `PagedServingEngine` must agree
with the reference's full forward pass, by the comparison that decides a
run's `correct` (`bench/check.py`); the fp8 control in the program's place
must not. At this size logits are small (hidden 64), so the limits and
the width of a wide gap (0.01) are the test configuration's own
(`data/tiny-*.json`), set like a cell's: between the program's highest
reading over twelve seeds on the CPU and the control's lowest. At this
size a top-2 of 4 experts flips on bfloat16 rounding often enough that the
MoE's widest gap does not separate from the control's; its share of wide
gaps does (at most 0.021 against at least 0.153), and so does its mean gap
over the seeds below. The dense files' program reads no gap over 0.01 on
any of the twelve seeds, the control at least 0.059 of them, so their
widest gap is compared too.
"""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import check
from bench import weights as W
from bench.drivers.serve import program_config, program_params
from bench.run import load_family

DATA = Path(__file__).resolve().parent / "data"
CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def serve(cfg, seed, n_req=6, gen=24):
    from repro.models import build_model
    from repro.serve import PagedServingEngine

    pcfg = program_config(cfg)
    spec = load_family(cfg).leaves(cfg)
    params = program_params(spec, W.make_params(spec, seed), build_model(pcfg))
    eng = PagedServingEngine(pcfg, block_size=16, num_blocks=256, params=params,
                             max_in_flight=4)
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg["vocab_size"], 40).tolist()
    prompts = []
    for i in range(n_req):
        body = rng.integers(0, cfg["vocab_size"], int(rng.integers(8, 70))).tolist()
        prompts.append(shared + body if i % 2 else body)
    rids = [eng.submit(p, max_new_tokens=gen) for p in prompts]
    stats = eng.run()
    assert stats["completed"] == n_req
    recs = [SimpleNamespace(rid=r, prompt=p, tokens=eng.request(r).generated)
            for r, p in zip(rids, prompts)]
    return recs, stats


@pytest.mark.parametrize("name", ["tiny-dense", "tiny-moe"])
@pytest.mark.parametrize("seed", [0, 2**32 + 11])
def test_served_tokens_match_the_reference_and_the_control_does_not(name, seed):
    cfg = json.loads((DATA / f"{name}.json").read_text())
    recs, stats = serve(cfg, seed)
    assert stats["prefix_hits"] > 0 and stats["cow_forks"] > 0
    out = check.compare(load_family(cfg), cfg, seed, recs, pad_to=512, control=True)
    print(name, seed, out)
    assert out["tokens_compared"] == sum(len(r.tokens) for r in recs)
    for key, limit in cfg["check"]["limits"].items():
        assert out[key] <= limit < out["control_" + key]


def test_a_wrong_token_is_caught():
    cfg = json.loads((DATA / "tiny-dense.json").read_text())
    fam = load_family(cfg)
    recs, _ = serve(cfg, 5)
    assert check.judge(cfg, check.compare(fam, cfg, 5, recs, pad_to=512), 0)[0]
    recs[0].tokens = list(recs[0].tokens)
    ref = fam.logits(cfg, 5, *check._inputs(recs[:1]), pad_to=512)[0]
    recs[0].tokens[3] = int(np.argsort(ref[3])[len(ref[3]) // 2])
    out = check.compare(fam, cfg, 5, recs, pad_to=512)
    assert out["logit_gap"] > 10 * cfg["check"]["wide_gap"]
    assert not check.judge(cfg, out, 0)[0]


@pytest.mark.parametrize("config", ["granite-moe-1b-a400m", "granite-3-2b"])
def test_a_wrong_token_a_request_fails_a_full_size_file(config):
    """About one wrong token a request among some thousand served (what
    `bench/faults.py`'s ``one:256`` plants) stays under the share of wide
    gaps; the widest gap's limit fails it. 3.65 is the least such a fault
    read on the MoE on a TPU v5e; the clean side's widest, 0.153, passes."""
    cfg = json.loads((CONFIGS / f"{config}.json").read_text())
    wide = cfg["check"]["wide_gap"]

    def judged(g):
        return check.judge(cfg, dict(check.numbers(g, wide), tokens_compared=g.size), 0)

    g = np.zeros(1000)
    g[::97] = wide / 2     # bfloat16 flips, under the wide gap
    g[[10, 400]] = 0.153 if config.startswith("granite-moe") else 0.054
    assert judged(g)[0]
    g[[250, 500, 750, 999]] = 3.65
    ok, checks = judged(g)
    assert checks["wide_gap_share"]["value"] <= checks["wide_gap_share"]["limit"]
    assert not ok and checks["logit_gap"]["value"] > checks["logit_gap"]["limit"]
