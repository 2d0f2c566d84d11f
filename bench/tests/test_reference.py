"""The plain reference against the served path at a CPU size.

Prefill (chunked, through the paged cache, with a prefix hit and its
copy-on-write fork) and then decode through `PagedServingEngine` must agree
with the reference's full forward pass, by the comparison that decides a
run's `correct` (`bench/check.py`); the fp8 control in the program's place
must not. At this size logits are small (hidden 64), so the limit is this
test configuration's own (`data/tiny-*.json`), set like a cell's: between
the program's reading over the seeds below and the control's. At this
size a top-2 of 4 experts flips on bfloat16 rounding often enough that the
MoE's widest gap does not separate from the control's; its mean gap does.
"""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import check
from bench import weights as W
from bench.drivers.serve import program_config, program_params

DATA = Path(__file__).resolve().parent / "data"


def serve(cfg, seed, n_req=6, gen=24):
    from repro.models import build_model
    from repro.serve import PagedServingEngine

    pcfg = program_config(cfg)
    params = program_params(W.make_params(cfg, seed), build_model(pcfg))
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
    out = check.compare(cfg, seed, recs, pad_to=512, control=True)
    print(name, seed, out)
    assert out["tokens_compared"] == sum(len(r.tokens) for r in recs)
    for key, limit in cfg["check"]["limits"].items():
        assert out[key] <= limit < out["control_" + key]


def test_a_wrong_token_is_caught():
    cfg = json.loads((DATA / "tiny-dense.json").read_text())
    recs, _ = serve(cfg, 5)
    recs[0].tokens = list(recs[0].tokens)
    ref = check.granite.logits(cfg, 5, *check._inputs(recs[:1]), pad_to=512)[0]
    recs[0].tokens[3] = int(np.argsort(ref[3])[len(ref[3]) // 2])
    limit = cfg["check"]["limits"]["logit_gap"]
    assert check.compare(cfg, 5, recs, pad_to=512)["logit_gap"] > 10 * limit
