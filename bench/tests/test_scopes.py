"""The scope and idle breakdown of `bench/scopes.py` and the
`round_host_ms` reader, on synthetic spans and on the recorded trace."""
from pathlib import Path

import pytest

from bench import scopes as S
from bench import trace_reduce as R
from bench.run import BENCH, Run, Span, load_module

FIXTURE = Path(__file__).resolve().parent / "data" / "trace_fixture.xplane.pb"


def _reader():
    return load_module(BENCH / "metrics" / "round_host_ms.py",
                       "bench_metric_round_host_ms")


def _run(spans):
    return Run(cfg={}, sizes=None, peaks={}, driver=None,
               spans=[Span(n, a, b, {}) for n, a, b in spans])


def test_round_host_ms_is_each_round_less_its_waits():
    spans = [("round", 0.0, 10.0), ("decode_round", 1.0, 6.5),
             ("decode_wait", 2.0, 6.0), ("prefill_wait", 7.0, 9.0),
             ("round", 20.0, 25.0), ("decode_wait", 21.0, 24.0),
             ("prefill_wait", 30.0, 31.0)]      # in no round: not counted
    # (10 - 4 - 2) and (5 - 3) seconds of host time: 3 s a round
    assert _reader().read(_run(spans)) == pytest.approx(3000.0)


def test_round_host_ms_is_silent_without_wait_spans():
    """A program that records no wait spans (the parent's) gives nothing."""
    spans = [("round", 0.0, 10.0), ("decode_round", 1.0, 6.0),
             ("prefill_chunk", 7.0, 9.0)]
    assert _reader().read(_run(spans)) is None
    assert _reader().read(_run([])) is None


def test_idle_is_split_by_the_innermost_span():
    spans = [("round", 0, 100), ("decode_stage", 10, 30),
             ("cow_fork", 12, 14), ("emit", 60, 70)]
    idle = [(5, 15), (65, 80), (200, 210)]
    assert S.idle_by_span(idle, spans) == {
        "round": 5 + 10, "decode_stage": 2 + 1, "cow_fork": 2, "emit": 5,
        "(none)": 10}


def test_in_round_idle_is_what_busy_leaves_of_the_rounds():
    busy = [(2, 4), (6, 12), (20, 30)]
    assert S.in_round_idle(busy, [(0, 10), (15, 25)]) == [
        (0, 2), (4, 6), (15, 20)]


def test_by_scope_sums_parts_and_flags_unmatched():
    ops = {"fusion.1": 3.0, "fusion.2": 2.0, "copy.3": 4.0, "dot.4": 1.0}
    scopes = {"fusion.1": "attention/kv_append", "fusion.2": "attention/qkv",
              "copy.3": "other"}
    assert S.by_scope(ops, scopes) == {"attention": 5.0, "other": 4.0,
                                       "unmatched": 1.0}
    assert S.by_scope(ops, scopes, 2)["attention/kv_append"] == 3.0


def test_instruction_name_of_a_trace_op():
    assert S.instruction("%copy.88 = bf16[1,7001,16,8,64]{4,3,2,1,0} "
                         "copy(%p)") == "copy.88"
    assert S.instruction("fusion.3") == "fusion.3"


def test_breakdown_of_the_recorded_trace():
    pd = R.load(str(FIXTURE))
    out = S.breakdown(pd, {})
    red = R.reduce(pd)
    for prog in ("paged_decode_round", "paged_prefill_chunk"):
        p = out["programs"][prog]
        assert p["runs"] == 4
        assert 0 < p["op_ms_per_run"] <= p["module_ms_per_run"] * 1.001
        # no op_scopes given: every operation is unmatched
        assert set(p["scope_ms_per_run"]) == {"unmatched"}
        assert p["op_ms_per_run"] * 4 * 1e6 == pytest.approx(
            red["programs"][prog], rel=0.2)
    idle = out["idle"]
    assert idle["in_round_idle_ms"] * 1e6 == pytest.approx(
        red["round_ns"] - red["busy_in_rounds_ns"])
    # the recorded trace holds no `repro/` spans: all idle is under none
    assert idle["under_child_span_share"] == 0.0
    assert set(idle["by_span_ms"]) == {"(none)"}
