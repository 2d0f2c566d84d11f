"""A block family for the tests only: granite's block with an untied head.

It shows what a configuration of a block the benchmark has not served
before brings as files of its own: the leaves (granite's, and the head
``lm_head`` ``[d, vocab]``, which the program holds apart from the
embedding when `tie_word_embeddings` is false), the sizes (the head's
parameters counted once more), and the reference (granite's blocks,
`granite.hidden`, then the final norm's output multiplied by ``lm_head``).
"""
from __future__ import annotations

import functools

import jax

from bench import work
from bench.reference import granite as G


def leaves(cfg: dict):
    spec = G.leaves(cfg)
    m = G.dims(cfg)
    head = (1002, (m["d"], m["V"]), "fan_in", G.EMBED_STD)
    return spec._replace(glob={**spec.glob, "lm_head": head})


def sizes(cfg: dict) -> work.Sizes:
    s = G.sizes(cfg)
    return s._replace(head_params=s.head_params + s.unembed_params)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def untied_head(g, x, *, m, quant):
    hn = G.rms_norm(x, g["final_norm"], dict(m)["eps"])
    return G._q(hn, quant) @ G._wq(g["lm_head"], quant, (0,))


def logits(cfg, seed, seqs, positions, *, pad_to, quant=None):
    with jax.default_matmul_precision("highest"):
        g, rows, key = G.hidden(cfg, leaves(cfg), seed, seqs, positions,
                                pad_to=pad_to, quant=quant)
        return G.split(untied_head(g, rows, m=key, quant=quant), positions)
