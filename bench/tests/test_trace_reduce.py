"""The trace reduction, on interval arithmetic and on a small trace
recorded on a TPU v5e by `data/record_trace.py` (committed)."""
from pathlib import Path

import pytest

from bench import trace_reduce as R

FIXTURE = Path(__file__).resolve().parent / "data" / "trace_fixture.xplane.pb"


def test_union_and_intersection():
    assert R.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert R.length(R.union([(0, 2), (1, 3), (3, 4)])) == 4
    assert R.intersect([(0, 3), (5, 6)], [(2, 5.5)]) == [(2, 3), (5, 5.5)]
    assert R.intersect([(0, 1)], [(2, 3)]) == []


def test_clock_offset_is_the_median_of_matched_rounds():
    rounds = [(0, 1000.0, 2000.0), (1, 3000.0, 4000.0), (2, 9000.0, 9100.0)]
    host = {0: 0.5e-6, 1: 2.5e-6, 2: 3.0e-6}   # round 2's annotation is late
    assert R.clock_offset_ns(rounds, host) == pytest.approx(500.0)


def test_recorded_trace():
    pd = R.load(str(FIXTURE))
    out = R.reduce(pd)
    assert [n for n, _, _ in out["rounds"]] == [0, 1, 2, 3]
    # both programs ran on the device, inside the rounds
    assert set(out["programs"]) == {"paged_decode_round", "paged_prefill_chunk"}
    assert sorted(out["program_rounds"]["paged_decode_round"]) == [0, 1, 2, 3]
    assert 0 < out["busy_in_rounds_ns"] <= out["busy_ns"]
    assert out["busy_in_rounds_ns"] < out["round_ns"]    # the host sleeps in rounds
    # each round sleeps 2 ms between its two programs: an idle gap of about that
    assert out["gaps"] and out["gaps"][0][1] >= 1.5e-3
    assert out["top_ops"] and all(v > 0 for _, v in out["top_ops"])
    names = {k.split("/")[0] for k, _ in out["top_ops"]}
    assert names & {"paged_decode_round", "paged_prefill_chunk"}


def test_gaps_are_named_by_the_host_span_open():
    pd = R.load(str(FIXTURE))
    rounds = R.events(pd)[2]
    # host spans on a clock 10 s behind the trace's, one per round's middle
    off = 10.0
    host_starts = {n: s / 1e9 - off for n, s, _ in rounds}
    spans = [("host_work", s / 1e9 - off, e / 1e9 - off) for _, s, e in rounds]
    out = R.reduce(pd, host_spans=spans, host_round_starts=host_starts)
    assert out["gaps"][0][0] == "host_work"
