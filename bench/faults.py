"""Faults planted in the program's decode round, to read what the
correctness check makes of a broken timed path.

`plant(kind)` replaces `repro.serve.engine.decode_round_fn`, which the
engine compiles the round from, so the window drives the broken round and
the engine feeds its tokens back as it would its own:

* ``token``: a token altered where it is produced (every row's next token
  shifted by one);
* ``state``: a step that returns its state unchanged (the round's new keys
  and values are dropped: the pools come back as they went in);
* ``half``: half of the batch left out (of the rows in use, the second
  half get the first half's tokens);
* ``one:<period>``: one token in `period` altered (shifted by one), where
  a row's context length is a multiple of `period`: about one wrong token
  a request when `period` is near the median output, each followed by
  tokens served after it as the program would.

`bench/tests/test_faults.py` runs them at a CPU size;
`bench/calibrate.py control --fault <kind>` reads them on the chip at a
cell's own size.
"""
from __future__ import annotations

import jax.numpy as jnp


def broken(kind: str, good):
    """A `decode_round_fn` whose rounds break as `kind` says."""
    name, _, arg = kind.partition(":")
    if name not in ("token", "state", "half", "one"):
        raise ValueError(f"unknown fault {kind!r}")
    period = int(arg) if name == "one" else 0

    def decode_round_fn(model):
        body = good(model)

        def paged_decode_round(params, k_pools, v_pools, tokens, tables, lengths):
            nxt, finite, k2, v2 = body(params, k_pools, v_pools, tokens, tables,
                                       lengths)
            if name == "token":
                nxt = (nxt + 1) % model.cfg.vocab
            elif name == "one":
                hit = (lengths > 0) & (lengths % period == 0)
                nxt = jnp.where(hit, (nxt + 1) % model.cfg.vocab, nxt)
            elif name == "state":
                k2, v2 = k_pools, v_pools
            else:
                # of the rows in use (padding rows have length 0), the
                # second half repeat the first half's tokens
                n = jnp.sum(lengths > 0)
                i = jnp.arange(nxt.shape[0])
                nxt = nxt[jnp.where((i >= n // 2) & (i < n), i - n // 2, i)]
            return nxt, finite, k2, v2

        return paged_decode_round

    return decode_round_fn


def plant(kind: str, setattr=setattr) -> None:
    """Break the engine's decode round as `kind` says (`setattr` may be a
    test's `monkeypatch.setattr`, which undoes it)."""
    import repro.serve.engine as engine_mod

    setattr(engine_mod, "decode_round_fn", broken(kind, engine_mod.decode_round_fn))
