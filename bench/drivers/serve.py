"""Window driver of the serving cells.

It builds the program's paged server (`repro.serve.PagedServingEngine`)
from a configuration file, with weights the benchmark draws from the seed
as the configuration's block family declares them, and drives it through
`submit` and `step_round` only:

1. set-up: weights and engine, then warm-up requests that compile every
   program the window runs (both prefill chunk buckets, the decode round,
   a prefix hit inside a cached block with its copy-on-write fork, which
   any two prompts that begin with the same token make) and put the mix's shared
   system prompts in the prefix cache, as a running server holds them;
2. lead-in: the mix's traffic starts `lead_s` before the window, so the
   window opens on a loaded server (a backlog instead runs until every row
   of the round is taken and half of them decode);
3. the window: `seconds` of open-loop arrivals; each request is timed from
   the moment it was due, so a late sender or a stalled round counts;
4. drain: no new arrivals; requests due in the window run until each has
   its first token, up to `drain_cap_s`; one the engine failed, or one
   still without a token then, has failed.

Requests ask for the whole rest of the context (`max_new_tokens` = the
mix's context cap minus the prompt), as an API does by default, and the
client stops each at the output length it drew: the engine models no end
of sequence, and the stop stands in for it. Every request of a mix then
needs the same block-table width, so the window runs the programs the
warm-up compiled. (The program keys its decode and prefill programs by the
exact table width in blocks; a drawn `max_new_tokens` would need one
program per width a mix can reach, tens to hundreds of compiles to warm.)
"""
from __future__ import annotations

import dataclasses
import gc
import heapq
import time
from typing import Dict, List, Optional

import numpy as np

from bench import traffic as T
from bench import weights as W

clock = time.perf_counter


@dataclasses.dataclass
class Rec:
    """One request as the client saw it."""
    rid: int
    tag: str                 # warm | lead | window | late
    due: float
    prompt: List[int]
    target: int              # output length the client stops at
    submit: float = 0.0
    times: List[float] = dataclasses.field(default_factory=list)
    end: Optional[float] = None
    state: str = "open"      # open | done | failed
    reason: str = ""         # the engine's finish reason
    tokens: List[int] = dataclasses.field(default_factory=list)
    session: Optional[int] = None


@dataclasses.dataclass
class Round:
    start: float
    end: float
    decode_ctx: List[int]    # positions each decode row attended


def program_config(cfg: dict):
    """The program's ArchConfig, set from the configuration file: the file
    holds the configuration as it is run."""
    from repro.configs import get_config

    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    f = cfg["intermediate_size"]
    moe = dict(moe=True, n_experts=cfg["num_local_experts"],
               top_k=cfg["num_experts_per_tok"], d_ff_expert=f) \
        if cfg.get("num_local_experts") else {}
    return get_config(cfg["program_arch"]).replace(
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=h,
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg.get("head_dim") or d // h,
        d_ff=f, vocab=cfg["vocab_size"], norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"], tie_embeddings=cfg["tie_word_embeddings"],
        dtype="bfloat16", param_dtype="bfloat16", cache_update="row", **moe)


def program_params(spec: W.Leaves, canon: Dict, model):
    """The benchmark's weights in the program's parameter tree, checked
    leaf by leaf against the tree the program declares: a global leaf at
    the top, a per-layer ``group.leaf`` under ``layers``."""
    import jax

    tree = {name: canon[name] for name in spec.glob}
    tree["layers"] = {}
    for name in spec.per_layer:
        arr = canon[name]
        group, _, leaf = name.partition(".")
        if leaf:
            tree["layers"].setdefault(group, {})[leaf] = arr
        else:
            tree["layers"][group] = arr
    want = model.abstract_params()
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (w.shape, w.dtype) != (g.shape, g.dtype)
            for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the program's "
                         f"parameter tree:\nwant {want}\ngot {got}")
    return tree


class ServeDriver:
    def __init__(self, cfg: dict, family, mix: dict, seed: int, seconds: float, *,
                 trace_s: float = 0.0):
        """`family` is the configuration's block family module
        (`bench.run.load_family`), which declares the weights' leaves."""
        self.cfg, self.family, self.mix, self.seed = cfg, family, mix, int(seed)
        self.seconds = float(seconds)
        self.trace_s = float(trace_s)
        self.cap = mix["context_cap"]
        self.recs: Dict[int, Rec] = {}
        self.rounds: List[Round] = []
        self._round: Optional[Round] = None
        self._stop: List[int] = []
        self.open = self.close = None
        self._trace_at: Optional[float] = None
        self._heap: list = []
        self.trace_window = None      # (start, stop) of the profiler trace
        self.profile_dir = None

    # ------------------------------------------------------------ set-up

    def build(self):
        import jax

        from repro.core import guard
        from repro.models import build_model
        from repro.serve import PagedServingEngine

        guard.set_strict(True)
        serve = self.cfg["serve"]
        pcfg = program_config(self.cfg)
        spec = self.family.leaves(self.cfg)
        params = program_params(spec, W.make_params(spec, self.seed),
                                build_model(pcfg))
        jax.block_until_ready(params)
        self.engine = PagedServingEngine(
            pcfg, block_size=serve["block_size"],
            num_blocks=serve["num_blocks"], params=params,
            on_token=self._on_token, on_finish=self._on_finish)
        self.memory_after_build = jax.devices()[0].memory_stats() or {}
        self.traffic = T.build(self.mix, self.seed, self.cfg["vocab_size"],
                               self._horizon())

    def _horizon(self) -> float:
        return self.mix.get("lead_s", 0.0) + self.seconds

    # --------------------------------------------------------- callbacks

    def _on_token(self, req, token: int) -> None:
        from repro.serve.scheduler import RequestState

        rec = self.recs[req.rid]
        rec.times.append(clock())
        if req.state is RequestState.RUNNING and self._round is not None:
            self._round.decode_ctx.append(int(req.kv_len))
        if len(req.generated) >= rec.target:
            self._stop.append(req.rid)

    def _on_finish(self, req) -> None:
        rec = self.recs[req.rid]
        rec.end = clock()
        rec.reason = req.finish_reason or ""
        rec.tokens = list(req.generated[:rec.target])
        ok = (req.finish_reason in ("stop", "complete")
              and len(rec.tokens) == rec.target)
        rec.state = "done" if ok else "failed"
        if rec.session is not None and ok:
            self._next_turn(rec)

    # ----------------------------------------------------------- intake

    def submit(self, prompt: List[int], target: int, due: float, tag: str,
               session: Optional[int] = None) -> Rec:
        now = clock()
        rid = self.engine.submit(prompt, max_new_tokens=self.cap - len(prompt))
        rec = Rec(rid, tag, due, list(prompt), int(target), submit=now,
                  session=session)
        self.recs[rid] = rec
        return rec

    def _tag(self, due: float) -> str:
        if self.open is None or due < self.open:
            return "lead"
        return "window" if due < self.close else "late"

    # arrivals ---------------------------------------------------------

    def _start_traffic(self, t0: float) -> None:
        """Queue each session's first turn: at its start time (poisson), or
        when the backlog asks for more (`_submit_due`)."""
        self._hist: Dict[int, List[int]] = {}
        self._turn: Dict[int, int] = {}
        self._next_session = 0
        self._heap = []
        if self.traffic.process == "poisson":
            self._heap = [(t0 + s.start_s, i, i, 0)
                          for i, s in enumerate(self.traffic.sessions)]
            heapq.heapify(self._heap)

    def _next_turn(self, rec: Rec) -> None:
        s = rec.session
        sess = self.traffic.sessions[s]
        k = self._turn[s]
        self._hist[s] = rec.prompt + rec.tokens
        if k + 1 >= len(sess.turns):
            return
        due = rec.end + sess.turns[k].think_s
        heapq.heappush(self._heap, (due, len(self.recs) + 10**6, s, k + 1))

    def _submit_turn(self, s: int, k: int, due: float) -> None:
        sess = self.traffic.sessions[s]
        if k == 0:
            prompt = sess.prompt(self.traffic.system_prompts)
        else:
            prompt = self._hist[s] + sess.turns[k].user
            if len(prompt) >= self.cap:
                return              # the conversation outgrew the context
        self._turn[s] = k
        target = min(sess.turns[k].out_len, self.cap - len(prompt))
        self.submit(prompt, target, due, self._tag(due), session=s)

    def _submit_due(self, now: float) -> None:
        if self.traffic.process == "backlog":
            want = self.mix["arrivals"]["queue_factor"] * self.engine.round_width
            while len(self.engine.scheduler.waiting) < want:
                if self._next_session >= len(self.traffic.sessions):
                    raise RuntimeError("backlog exhausted: raise `requests`")
                self._submit_turn(self._next_session, 0, now)
                self._next_session += 1
        while self._heap and self._heap[0][0] <= now:
            due, i, s, k = heapq.heappop(self._heap)
            if self.close is not None and due >= self.close:
                heapq.heappush(self._heap, (due, i, s, k))
                return
            self._submit_turn(s, k, due)

    # ------------------------------------------------------------ rounds

    def _step(self, annotate: bool) -> None:
        r = Round(clock(), 0.0, [])
        self._round = r
        if annotate:
            import jax
            with jax.profiler.TraceAnnotation("round", n=len(self.rounds)):
                self.engine.step_round()
        else:
            self.engine.step_round()
        r.end = clock()
        self._round = None
        self.rounds.append(r)
        stop, self._stop = self._stop, []
        for rid in stop:
            self.engine.cancel(rid, reason="stop")

    def _run_until(self, until: float, *, arrivals: bool, done=None) -> None:
        tracing = False
        while True:
            now = clock()
            if now >= until or (done is not None and done()):
                break
            if self._trace_at is not None and now >= self._trace_at:
                self._trace_at = None
                self._start_profile()
                tracing = True
            if arrivals:
                self._submit_due(now)
            if self.engine.scheduler.has_work():
                self._step(annotate=tracing)
                continue
            nxt = self._heap[0][0] if (arrivals and self._heap) else until
            time.sleep(max(0.0, min(nxt, until) - clock()))
        if tracing:
            self._stop_profile()

    def _start_profile(self) -> None:
        import tempfile

        import jax

        self._tmp = tempfile.TemporaryDirectory(prefix="bench-profile-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self._tmp.name, profiler_options=opts)
        self.trace_window = (clock(), None)

    def _stop_profile(self) -> None:
        import jax

        t = clock()
        jax.profiler.stop_trace()
        self.trace_window = (self.trace_window[0], t)
        self.profile_dir = self._tmp.name

    # ------------------------------------------------------------ phases

    def warm_up(self) -> None:
        """Compile every program the window runs, outside the window."""
        rng = np.random.default_rng([self.seed, 1])
        V = self.cfg["vocab_size"]
        base = rng.integers(0, V, 56).tolist()
        prompts = [base[:40],                 # chunks of 32 and of 8 tokens
                   base,                      # 32 and 24
                   # a prefix hit that ends inside a cached block: the
                   # engine copy-on-write forks that page, and the eager
                   # page copy compiles here, not at the window's first fork
                   base[:20] + rng.integers(0, V, 9).tolist()]
        # shared system prompts enter the prefix cache, as a running
        # server holds them
        prompts += [p + rng.integers(0, V, 8).tolist()
                    for p in self.traffic.system_prompts]
        first = [self.submit(prompts[0], 4, clock(), "warm")]
        self._drain([r.rid for r in first], cap_s=600)
        rest = [self.submit(p, 4, clock(), "warm") for p in prompts[1:]]
        self._drain([r.rid for r in rest], cap_s=600)

    def _drain(self, rids, cap_s: float) -> None:
        t_end = clock() + cap_s
        self._run_until(t_end, arrivals=False,
                        done=lambda: all(self.recs[r].state != "open"
                                         for r in rids))
        bad = [(r, self.recs[r].reason) for r in rids
               if self.recs[r].state != "done"]
        if bad and self.open is None:
            raise RuntimeError(f"warm-up requests did not complete: {bad}")

    def run(self, t_process: float, compile_log) -> None:
        """Set-up, lead-in, window and drain; fills the records."""
        self.build()
        self.warm_up()
        gc.collect()
        gc.freeze()
        t0 = clock()
        self._start_traffic(t0)
        if self.traffic.process == "backlog":
            eng = self.engine
            def steady():
                # every row of the round taken, half of them decoding
                return (eng.scheduler.in_flight() == eng.round_width
                        and 2 * len(eng.scheduler.running) >= eng.round_width)

            self._run_until(t0 + 600, arrivals=True, done=steady)
            if not steady():
                raise RuntimeError("the backlog never filled every row")
            self.open = clock()
            self.close = self.open + self.seconds
        else:
            self.open = t0 + self.mix.get("lead_s", 0.0)
            self.close = self.open + self.seconds
            self._run_until(self.open, arrivals=True)
        self.setup_s = self.open - t_process
        self.backlog = [self._backlog()]
        if self.trace_s:
            self._trace_at = self.close - min(self.trace_s, self.seconds)
        self._run_until(self.close, arrivals=True)
        self.backlog.append(self._backlog())
        self.compiles = compile_log.between(self.open, self.close)
        if self.traffic.process != "backlog":
            # no new arrivals; every request due in the window gets its
            # first token, so its TTFT is known (a late answer is late, not
            # wrong: one still running then is not a failure)
            due = [self.recs[r] for r in self.recs if self.recs[r].tag == "window"]
            self._run_until(clock() + self.mix["drain_cap_s"], arrivals=False,
                            done=lambda: all(r.times or r.state != "open"
                                             for r in due))
        self.drained = clock()

    def _backlog(self) -> int:
        """Requests submitted and not yet ended."""
        return sum(r.state == "open" for r in self.recs.values())

    # --------------------------------------------------------- readings

    def window_recs(self) -> List[Rec]:
        return [r for r in self.recs.values() if r.tag == "window"]

    def end_to_end(self) -> Dict[str, float]:
        o, c = self.open, self.close
        gaps = [b - a for r in self.recs.values() if r.tag != "warm"
                for a, b in zip(r.times, r.times[1:]) if o <= b < c]
        out = {"setup_s": self.setup_s}
        if gaps:
            from bench.stats import percentile
            for q in (50, 90, 99):
                out[f"tbt_p{q}_ms"] = percentile(gaps, q / 100) * 1e3
            out["tbt_samples"] = len(gaps)
        if self.traffic.process == "backlog":
            n = sum(1 for r in self.recs.values() for t in r.times if o <= t < c)
            out["output_tok_s"] = n / (c - o)
        else:
            from bench.stats import percentile
            ttft = [(r.times[0] if r.times else self.drained) - r.due
                    for r in self.window_recs()]
            if ttft:
                for q in (50, 70, 75, 80, 90):
                    out[f"ttft_p{q}_ms"] = percentile(ttft, q / 100) * 1e3
                out["ttft_samples"] = len(ttft)
            late = [r.submit - r.due for r in self.window_recs()]
            if late:
                out["sender_late_p99_ms"] = percentile(late, 0.99) * 1e3
        return out

    def counts(self):
        """(attempted, failed): requests due in the window, or, for a
        backlog, those that ran in it."""
        if self.traffic.process == "backlog":
            o, c = self.open, self.close
            ran = [r for r in self.recs.values() if r.tag != "warm"
                   and r.times and r.times[-1] >= o and r.times[0] < c]
            return len(ran), sum(r.state == "failed" for r in ran)
        recs = self.window_recs()
        return len(recs), sum(r.state == "failed" or not r.times for r in recs)

    def finished(self) -> List[Rec]:
        """Requests that completed after set-up, for the correctness sample."""
        return [r for r in self.recs.values()
                if r.tag != "warm" and r.state == "done" and r.tokens]

    def free(self) -> None:
        """Drop the program's state so the reference has the chip."""
        self.engine = None
        gc.unfreeze()
        gc.collect()
