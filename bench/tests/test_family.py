"""The block family a configuration names (`"reference"`, found as
`bench/reference/<family>.py`): granite's weights and reference logits are
what they were before the family declared them, and a second family enters
the benchmark as files of its own.

The second family, `data/granite_untied.py` with `data/tiny-untied.json`,
is granite's block with an untied head: it adds a leaf (``lm_head``), its
own sizes and its own reference, and nothing outside its two files knows it.
"""
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

import repro.models.lm as lm
from bench import weights as W
from bench.run import BENCH, load_family, load_module

DATA = Path(__file__).resolve().parent / "data"
SEED = 2**32 + 7

# sha256 of the bf16 weights and of the float32 reference logits at SEED,
# as the benchmark drew and computed them before each family declared its
# own leaves (commit e02db59)
PARENT = {
    "weights tiny-moe": '93ac27ce4410e888b4495c5f215850073eab696481f885849cfffa2a832bda21',
    "weights tiny-dense": '9d3ba32ff2fe58e43809be1cb0e60263e48feaea1c7a99b136658074d697842c',
    "logits tiny-moe None": '0026369987c44d3aef76aef159665da3d1b07b3cb366547f74ff97d8146b1800',
    "logits tiny-moe fp8": '027a247dd152cb1693a2b60207625d43d1a59a6160a93079293d9b8507b00900',
    "logits tiny-dense None": '35052545555d68830d46590cfe712131d79975b39abe5c0e4ff73eb3c8b1517f',
    "logits tiny-dense fp8": '178b450fdd190022c75b34bb8df3b6045929c629662a9edd4f0a87262e4ee6f1',
}


def _cfg(name):
    return json.loads((DATA / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["tiny-moe", "tiny-dense"])
def test_granite_weights_are_what_they_were(name):
    cfg = _cfg(name)
    p = W.make_params(load_family(cfg).leaves(cfg), SEED)
    h = hashlib.sha256()
    for k in sorted(p):
        a = np.asarray(p[k])
        for part in (k, str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PARENT[f"weights {name}"]


@pytest.mark.parametrize("quant", [None, "fp8"])
@pytest.mark.parametrize("name", ["tiny-moe", "tiny-dense"])
def test_granite_reference_logits_are_what_they_were(name, quant):
    cfg = _cfg(name)
    V = cfg["vocab_size"]
    seqs = [[(7 * i + 3) % V for i in range(40)], [(11 * i + 5) % V for i in range(23)]]
    pos = [list(range(30, 39)), list(range(10, 22))]
    lg = load_family(cfg).logits(cfg, SEED, seqs, pos, pad_to=512, quant=quant)
    h = hashlib.sha256()
    for a in lg:
        a = np.asarray(a, np.float32)
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PARENT[f"logits {name} {quant}"]


def test_only_the_families_name_granite():
    """The harness finds the family by name and knows none of them."""
    for path in BENCH.rglob("*.py"):
        rel = path.relative_to(BENCH).parts
        if rel[0] in ("reference", "tests"):
            continue
        assert "granite" not in path.read_text(), path


def test_the_second_family_declares_its_head():
    cfg, tied = _cfg("tiny-untied"), _cfg("tiny-dense")
    fam = load_module(DATA / "granite_untied.py", "bench_reference_granite_untied")
    spec, base = fam.leaves(cfg), load_family(tied).leaves(tied)
    assert spec.glob["lm_head"][1] == (64, 256)
    assert spec.per_layer == base.per_layer
    assert {k: v for k, v in spec.glob.items() if k != "lm_head"} == base.glob
    assert fam.sizes(cfg).head_params == load_family(tied).sizes(tied).head_params + 64 * 256


def _tied_head(good):
    """The program's head with its own table ignored: the embedding's rows."""
    def unembed(params, x, cfg, ctx):
        return good({k: v for k, v in params.items() if k != "lm_head"}, x, cfg, ctx)
    return unembed


@pytest.mark.parametrize("head", ["untied", "tied"])
def test_a_second_family_is_files_only(tiny_checkout, run_tiny, monkeypatch, head):
    """Served as its file says, the untied family's run is correct; served
    with the head tied to the embedding, it is not."""
    root = tiny_checkout("tiny-untied", family="granite_untied")
    if head == "tied":
        monkeypatch.setattr(lm, "_unembed", _tied_head(lm._unembed))
    out = run_tiny(root, 2**32 + 3)
    assert out["correct"] is (head == "untied"), out["checks"]
