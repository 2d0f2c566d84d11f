"""Model FLOP/s utilization of the served steps: the operations the tokens
processed in the window need (prefilled tokens that the cache did not
serve, and decoded tokens, through every layer, with attention over their
live context; `bench/work.py`), over the wall time of the window's rounds
times the chip's peak. Time between rounds, waiting for arrivals, is not
in the denominator."""

from bench import work


def read(run):
    o, c = run.driver.open, run.driver.close
    rounds = [r for r in run.driver.rounds if o <= r.start and r.end <= c]
    wall = sum(r.end - r.start for r in rounds)
    if not wall:
        return None
    flops = sum(work.decode_flops(run.sizes, r.decode_ctx) for r in rounds)
    flops += sum(work.prefill_flops(run.sizes, s.args["start"], s.args["n"])
                 for s in run.spans if s.name == "prefill_chunk")
    return 100.0 * flops / (wall * run.peaks["bf16_flops_per_s"])
