"""A whole run with the timed path broken underneath must come out with
`correct` false.

Each case drives `bench/run.py`'s run of the cell
(`granite-moe-1b-a400m.offline-decode`, here on the CPU-size
`data/tiny-dense.json` or `data/tiny-moe.json`) with no look for a chip,
and breaks the program's decode round, which the window drives, in one of
the ways a serving step can go wrong (`bench/faults.py`): a token altered
where it is produced, every token or about one a request; a step that
returns its state unchanged; half of the batch left out. A one-chip cell
has no exchange between chips to leave out. The clean run comes out
correct, so what fails is the fault and not the harness.
"""
import json

import pytest

from bench import faults
from bench import run as bench_run

# one token in 32 context positions: about one a request at this size
# (outputs 16-48 tokens)
ONE_A_REQUEST = "one:32"


@pytest.fixture
def tiny_root(tiny_checkout):
    """The benchmark at CPU size, on `data/tiny-dense.json`, whose limits
    separate a sound run from a broken one at this size."""
    return tiny_checkout("tiny-dense")


def test_the_warm_up_forks_a_shared_page(tiny_root):
    """Two prompts that begin alike share a cached page that the second
    forks before it writes: the warm-up makes such a fork, so the page copy
    compiles in set-up and not inside the window."""
    from bench.drivers.serve import ServeDriver

    cfg = json.loads((bench_run.ROOT / "bench" / "tests" / "data"
                      / "tiny-dense.json").read_text())
    mix = json.loads((tiny_root / "bench" / "traffic" / "offline-decode.json")
                     .read_text())
    drv = ServeDriver(cfg, bench_run.load_family(cfg), mix, 2**31 + 5, 1.0)
    drv.build()
    drv.warm_up()
    assert drv.engine.cow_forks >= 1


@pytest.mark.parametrize("config, kind", [
    ("tiny-dense", None), ("tiny-dense", "token"), ("tiny-dense", "state"),
    ("tiny-dense", "half"), ("tiny-dense", ONE_A_REQUEST),
    # under the MoE file's limits, whose widest gap is not compared (a
    # top-2 of 4 experts flips too wide at this size)
    ("tiny-moe", None), ("tiny-moe", ONE_A_REQUEST)])
def test_a_broken_decode_round_is_not_correct(tiny_checkout, run_tiny, monkeypatch,
                                              config, kind):
    if kind is not None:
        faults.plant(kind, monkeypatch.setattr)
    out = run_tiny(tiny_checkout(config), 2**32 + 3)
    assert out["correct"] is (kind is None), out["checks"]
