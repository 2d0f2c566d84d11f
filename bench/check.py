"""What decides `correct`: the served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests the server finished (the longest, and others drawn from the
seed) is run through `bench/reference/granite.py` in float32: each prompt
with the tokens the server gave, in one causal pass. At every served
position the reference's logits say how good the served token was; the
number compared is the widest gap by which a served token's logit lies
below the reference's best token at that position. Greedy decoding in the
program's bfloat16 leaves only near-ties to flip, so the gap stays small;
a wrong cache page, position, head or expert puts it at the size of the
logits themselves.

The control is the reference itself in fp8 (`quant="fp8"`) in the
program's place: at the same positions it picks its own best token, and
its gap is read the same way. `bench/calibrate.py` reads both on the chip.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from bench.reference import granite


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


def compare(cfg: dict, seed: int, recs: Sequence, pad_to: int, *,
            control: bool = False) -> Dict[str, Optional[float]]:
    """Widest served-token gap of `recs` (and of the fp8 control)."""
    if not recs:   # nothing finished: nothing compared, and not correct
        none = dict.fromkeys(("logit_gap", "mean_gap", "control_logit_gap",
                              "control_mean_gap"))
        return dict(none, tokens_compared=0, requests_compared=0)
    seqs, pos = _inputs(recs)
    ref = granite.logits(cfg, seed, seqs, pos, pad_to=pad_to)
    served = gaps(ref, [r.tokens for r in recs])
    out = {"logit_gap": float(served.max()) if served.size else None,
           "mean_gap": float(served.mean()) if served.size else None,
           "tokens_compared": int(served.size),
           "requests_compared": len(recs)}
    if control:
        ctl = granite.logits(cfg, seed, seqs, pos, pad_to=pad_to, quant="fp8")
        cg = gaps(ref, [c.argmax(-1) for c in ctl])
        out["control_logit_gap"] = float(cg.max()) if cg.size else None
        out["control_mean_gap"] = float(cg.mean()) if cg.size else None
    return out
