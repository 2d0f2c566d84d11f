"""A whole run with the timed path broken underneath must come out with
`correct` false.

Each case drives `bench/run.py`'s run of the cell
(`granite-moe-1b-a400m.offline-decode`, here on the CPU-size
`data/tiny-dense.json`) with no look for a chip, and breaks the program's
decode round, which the window drives, in one of the ways a serving step
can go wrong:

* ``token``: a token altered where it is produced (every row's next token
  shifted by one);
* ``state``: a step that returns its state unchanged (the round's new keys
  and values are dropped: the pools come back as they went in);
* ``half``: half of the batch left out (of the rows in use, the second
  half get the first half's tokens).

A one-chip cell has no exchange between chips to leave out. The clean run
comes out correct, so what fails is the fault and not the harness.
"""
import json
import time

import jax.numpy as jnp
import pytest

import repro.serve.engine as engine_mod
from bench import run as bench_run
from bench import traffic


def broken(kind):
    good = engine_mod.decode_round_fn

    def decode_round_fn(model):
        body = good(model)

        def paged_decode_round(params, k_pools, v_pools, tokens, tables, lengths):
            nxt, finite, k2, v2 = body(params, k_pools, v_pools, tokens, tables,
                                       lengths)
            if kind == "token":
                nxt = (nxt + 1) % model.cfg.vocab
            elif kind == "state":
                k2, v2 = k_pools, v_pools
            elif kind == "half":
                # of the rows in use (padding rows have length 0), the
                # second half repeat the first half's tokens
                n = jnp.sum(lengths > 0)
                i = jnp.arange(nxt.shape[0])
                nxt = nxt[jnp.where((i >= n // 2) & (i < n), i - n // 2, i)]
            return nxt, finite, k2, v2

        return paged_decode_round

    return decode_round_fn


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout of the benchmark at CPU size: the configuration is
    `data/tiny-dense.json` (whose limits separate a sound run from a broken
    one at this size; the tiny MoE's do not), and the cell's backlog keeps
    its shape with lengths a CPU serves in seconds."""
    root = bench_run.ROOT
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        c["file"] = str(root / "bench" / "tests" / "data" / "tiny-dense.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    mix = traffic.load_mix("offline-decode")
    mix["context_cap"] = 128
    mix["classes"] = [dict(mix["classes"][0],
                           user_len={"median": 24, "sigma": 0.5, "lo": 8, "hi": 64},
                           output_len={"median": 24, "sigma": 0.3, "lo": 16, "hi": 48})]
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    (tmp_path / "bench" / "traffic" / "offline-decode.json").write_text(json.dumps(mix))
    return tmp_path


def run_once(root, seed):
    args = bench_run.parse(["--workload", "granite-moe-1b-a400m.offline-decode",
                            "--seed", str(seed), "--seconds", "3", "--trace", "0"])
    return bench_run.run_cell(args, root=root, require_chip=False,
                              t_process=time.perf_counter())


def test_the_warm_up_forks_a_shared_page(tiny_root):
    """Two prompts that begin alike share a cached page that the second
    forks before it writes: the warm-up makes such a fork, so the page copy
    compiles in set-up and not inside the window."""
    from bench.drivers.serve import ServeDriver

    cfg = json.loads((bench_run.ROOT / "bench" / "tests" / "data"
                      / "tiny-dense.json").read_text())
    mix = json.loads((tiny_root / "bench" / "traffic" / "offline-decode.json")
                     .read_text())
    drv = ServeDriver(cfg, mix, 2**31 + 5, 1.0)
    drv.build()
    drv.warm_up()
    assert drv.engine.cow_forks >= 1


@pytest.mark.parametrize("kind", [None, "token", "state", "half"])
def test_a_broken_decode_round_is_not_correct(tiny_root, monkeypatch, kind):
    if kind is not None:
        monkeypatch.setattr(engine_mod, "decode_round_fn", broken(kind))
    out = run_once(tiny_root, 2**32 + 3)
    assert out["correct"] is (kind is None), out["checks"]
