"""What decides `correct`: the served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests the server finished (the longest, and others drawn from the
seed) is run through the configuration's plain reference in float32 (its
block family's `logits`, `bench/reference/<family>.py`): each prompt with
the tokens the server gave, in one causal pass. At every served position
the reference's logits say how good the served token was: the gap by which
its logit lies below the reference's best token there. Greedy decoding in
the program's bfloat16 leaves only near-ties to flip, so most gaps are 0
and the rest small; a wrong cache page, position, head or expert puts them
at the size of the logits themselves.

Three numbers are read from the gaps: the widest (`logit_gap`), their
mean (`mean_gap`), and the share of positions whose gap is wider than the
configuration's `wide_gap` (`wide_gap_share`); the configuration's limits
say which of them are compared. A bfloat16 program flips only near-ties,
and its flips seldom cost more than a few hundredths of a logit; but how
many near-ties a sample holds swings from seed to seed, and now and then
one flip costs a tenth or more. Then the widest gap and the mean of a
sound MoE run read what the fp8 control reads on another seed (PERF.md,
§2). The control flips wider gaps on every seed, so the share of wide
gaps parts the two. A share lets a few wrong tokens among a thousand
pass, so the widest gap stays compared beside it, as a cap far above any
flip: a wrong token lies logits, not hundredths, below the best, and a
fault that plants about one a request (`bench/faults.py`) sets how high
the cap may go.

The control is the reference itself in fp8 (`quant="fp8"`) in the
program's place: at the same positions it picks its own best token, and
its gaps are read the same way. `bench/calibrate.py` reads both on the chip.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

NUMBERS = ("logit_gap", "mean_gap", "wide_gap_share")


def sample(finished: Sequence, seed: int, n: int) -> List:
    """The longest finished request and up to n - 1 others, drawn from
    the seed."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.prompt) + len(r.tokens), r.rid))
    others = sorted((r for r in finished if r is not longest), key=lambda r: r.rid)
    rng = np.random.default_rng([int(seed), 2])
    pick = rng.choice(len(others), size=min(n - 1, len(others)), replace=False)
    return [longest] + [others[i] for i in sorted(pick)]


def _inputs(recs):
    seqs = [list(r.prompt) + list(r.tokens[:-1]) for r in recs]
    pos = [list(range(len(r.prompt) - 1, len(r.prompt) + len(r.tokens) - 1))
           for r in recs]
    return seqs, pos


def gaps(ref: Sequence[np.ndarray], tokens: Sequence[Sequence[int]]) -> np.ndarray:
    """Per position: the reference's best logit minus the logit of `tokens`."""
    out = []
    for lg, tok in zip(ref, tokens):
        lg = np.asarray(lg, np.float64)
        out.append(lg.max(-1) - lg[np.arange(len(tok)), np.asarray(tok)])
    return np.concatenate(out) if out else np.zeros(0)


def numbers(g: np.ndarray, wide: float) -> Dict[str, Optional[float]]:
    """The numbers read from one side's gaps; `wide` is the gap above which
    a position counts in `wide_gap_share`."""
    if not g.size:
        return dict.fromkeys(NUMBERS)
    return {"logit_gap": float(g.max()), "mean_gap": float(g.mean()),
            "wide_gap_share": float(np.mean(g > wide))}


def judge(cfg: dict, comp: Dict, failed: int, *, prefix: str = ""):
    """(correct, checks): each number of the configuration's limits, read
    from `comp` under `prefix` (``"control_"`` for the control's), beside
    its limit, with the failed requests."""
    limits = cfg["check"]["limits"]
    checks = {k: {"value": comp[prefix + k], "limit": lim} for k, lim in limits.items()}
    checks["failed"] = {"value": failed, "limit": 0}
    checks["tokens_compared"] = {"value": comp["tokens_compared"],
                                 "limit": cfg["check"]["min_tokens"]}
    ok = all(v["value"] is not None and v["value"] <= v["limit"]
             for k, v in checks.items() if k != "tokens_compared")
    ok = ok and comp["tokens_compared"] >= cfg["check"]["min_tokens"]
    return bool(ok), checks


def compare(family, cfg: dict, seed: int, recs: Sequence, pad_to: int, *,
            control: bool = False) -> Dict[str, Optional[float]]:
    """The served-token gaps of `recs` against `family`'s reference (and
    the fp8 control's), read as `numbers`; the gaps themselves under
    ``gaps`` (and ``control_gaps``)."""
    wide = cfg["check"]["wide_gap"]
    if not recs:   # nothing finished: nothing compared, and not correct
        none = numbers(np.zeros(0), wide)
        return dict(none, **{"control_" + k: v for k, v in none.items()},
                    tokens_compared=0, requests_compared=0,
                    gaps=np.zeros(0), control_gaps=np.zeros(0))
    seqs, pos = _inputs(recs)
    ref = family.logits(cfg, seed, seqs, pos, pad_to=pad_to)
    served = gaps(ref, [r.tokens for r in recs])
    out = dict(numbers(served, wide), tokens_compared=int(served.size),
               requests_compared=len(recs), gaps=served)
    if control:
        ctl = family.logits(cfg, seed, seqs, pos, pad_to=pad_to, quant="fp8")
        cg = gaps(ref, [c.argmax(-1) for c in ctl])
        out.update({"control_" + k: v for k, v in numbers(cg, wide).items()})
        out["control_gaps"] = cg
    return out
