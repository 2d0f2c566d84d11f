"""The window driver against a stand-in engine: open-loop due times,
sessions that carry their history, stops at the drawn length, and the
end-to-end arithmetic (a rate over the whole window, tails over every
sample)."""
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import traffic as T
from bench.drivers import serve as S
from bench.run import BENCH, load_module
from bench.stats import percentile
from repro.serve.scheduler import RequestState

V = 1000
DATA = Path(__file__).resolve().parent / "data"


class FakeEngine:
    """Admits everything, emits one token per request per round."""

    def __init__(self, on_token, on_finish, round_s=0.002):
        self.on_token, self.on_finish = on_token, on_finish
        self.round_width = 4
        self.round_s = round_s
        self.live = {}
        self.next = 0
        self.max_new = {}
        self.scheduler = SimpleNamespace(
            waiting=[], running=[], has_work=lambda: bool(self.live),
            in_flight=lambda: len(self.scheduler.running))

    def submit(self, prompt, max_new_tokens):
        rid = self.next
        self.next += 1
        self.max_new[rid] = max_new_tokens
        req = SimpleNamespace(
            rid=rid, prompt=list(prompt), generated=[], kv_len=len(prompt),
            state=RequestState.WAITING, finish_reason=None)
        self.live[rid] = req
        self.scheduler.waiting.append(req)
        return rid

    def step_round(self):
        time.sleep(self.round_s)
        sched = self.scheduler
        while sched.waiting and len(sched.running) < self.round_width:
            req = sched.waiting.pop(0)
            req.state = RequestState.PREFILL
            sched.running.append(req)
        for req in list(sched.running):
            req.generated.append((req.rid * 7 + len(req.generated)) % V)
            req.kv_len += 1
            self.on_token(req, req.generated[-1])
            req.state = RequestState.RUNNING

    def cancel(self, rid, reason="cancelled"):
        req = self.live.pop(rid, None)
        if req is None:
            return False
        for q in (self.scheduler.waiting, self.scheduler.running):
            if req in q:
                q.remove(req)
        req.finish_reason = reason
        self.on_finish(req)
        return True


def drive(mix, seconds=0.4, seed=3):
    drv = S.ServeDriver({"vocab_size": V, "hidden_size": 8,
                         "num_attention_heads": 1, "num_key_value_heads": 1,
                         "num_hidden_layers": 1, "intermediate_size": 8,
                         "rms_norm_eps": 1e-5, "rope_theta": 1e4},
                        None, mix, seed, seconds)

    def build():
        drv.engine = FakeEngine(drv._on_token, drv._on_finish)
        drv.traffic = T.build(mix, seed, V, drv._horizon())

    drv.build = build
    drv.warm_up = lambda: None
    log = SimpleNamespace(between=lambda a, b: [])
    drv.run(time.perf_counter(), log)
    return drv


def test_open_loop_requests_are_timed_from_their_due_time():
    mix = dict(T.load_mix("short-unshared", DATA), lead_s=0.1, drain_cap_s=5.0,
               arrivals={"process": "poisson", "rate_per_s": 40.0})
    drv = drive(mix)
    recs = drv.window_recs()
    # the drain runs until every request due in the window has its first
    # token; those that ended were stopped at their drawn length
    assert recs and all(r.times and r.state != "failed" for r in recs)
    assert any(r.state == "done" for r in recs)
    for r in recs:
        assert drv.open <= r.due < drv.close
        assert r.submit >= r.due and r.times[0] > r.due
        if r.state == "done":
            assert len(r.tokens) == r.target
    e2e = drv.end_to_end()
    ttft = [r.times[0] - r.due for r in recs]
    assert e2e["ttft_p80_ms"] == pytest.approx(percentile(ttft, 0.8) * 1e3)
    assert e2e["ttft_samples"] == len(recs)
    assert drv.counts() == (len(recs), 0)


def test_every_request_asks_for_the_rest_of_the_context():
    mix = dict(T.load_mix("short-unshared", DATA), lead_s=0.1, drain_cap_s=5.0,
               arrivals={"process": "poisson", "rate_per_s": 40.0})
    drv = drive(mix)
    for rid, r in drv.recs.items():
        assert drv.engine.max_new[rid] == mix["context_cap"] - len(r.prompt)


def test_sessions_carry_their_history():
    base = T.load_mix("chat-sessions", DATA)
    cls = dict(base["classes"][0], think_s={"mean": 0.01},
               output_len={"median": 6, "sigma": 0.3, "lo": 2, "hi": 12},
               user_len={"median": 5, "sigma": 0.3, "lo": 2, "hi": 9})
    mix = dict(base, lead_s=0.05, drain_cap_s=10.0, classes=[cls],
               arrivals={"process": "poisson", "rate_per_s": 20.0})
    drv = drive(mix, seconds=0.5)
    by_session = {}
    for r in sorted(drv.recs.values(), key=lambda r: r.submit):
        by_session.setdefault(r.session, []).append(r)
    multi = [rs for s, rs in by_session.items() if s is not None and len(rs) > 1]
    assert multi, "no session reached a second turn"
    for rs in multi:
        sess = drv.traffic.sessions[rs[0].session]
        assert rs[0].prompt == sess.prompt(drv.traffic.system_prompts)
        for prev, cur, turn in zip(rs, rs[1:], sess.turns[1:]):
            assert cur.prompt == prev.prompt + prev.tokens + turn.user
            # the next turn is due one think time after the reply ended
            assert cur.due >= prev.end


def test_a_rate_is_all_tokens_over_the_whole_window():
    mix = dict(T.load_mix("offline-decode"),
               arrivals={"process": "backlog", "requests": 400, "queue_factor": 2},
               classes=[{"output_len": {"median": 8, "sigma": 0.3, "lo": 4, "hi": 16},
                         "user_len": {"median": 8, "sigma": 0.3, "lo": 4, "hi": 16}}])
    drv = drive(mix, seconds=0.3)
    n = sum(1 for r in drv.recs.values() for t in r.times
            if drv.open <= t < drv.close)
    assert drv.end_to_end()["output_tok_s"] == pytest.approx(
        n / (drv.close - drv.open))


def test_a_tail_is_taken_over_every_gap():
    drv = S.ServeDriver({}, None, {"context_cap": 10}, 0, 1.0)
    drv.open, drv.close, drv.setup_s, drv.drained = 0.0, 10.0, 1.0, 10.0
    drv.traffic = SimpleNamespace(process="poisson")
    # one request with 99 gaps of 10 ms, one with a single gap of 1 s
    a = S.Rec(0, "window", 0.0, [1], 100, times=[1 + 0.01 * i for i in range(100)])
    b = S.Rec(1, "window", 0.0, [1], 2, times=[2.0, 3.0])
    drv.recs = {0: a, 1: b}
    e2e = drv.end_to_end()
    assert e2e["tbt_samples"] == 100
    assert e2e["tbt_p99_ms"] == pytest.approx(1000.0)
    # the backlog cells read the same tail as a per-layer metric
    reader = load_module(BENCH / "metrics" / "tbt_p99_ms.backlog.py",
                         "bench_metric_tbt_p99_ms_backlog")
    assert reader.read(SimpleNamespace(driver=drv)) == e2e["tbt_p99_ms"]


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert percentile(xs, 0.9) == 91 and percentile(xs, 0.99) == 100
    assert percentile([5.0], 0.99) == 5.0
    with pytest.raises(ValueError):
        percentile([], 0.5)
