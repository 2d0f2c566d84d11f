"""Window driver of the afmoe (Trinity) serving cells.

The serving driver (`serve.py`) as it is, with three things of its own:
the program's ArchConfig gets the fields `serve.program_config` does not
set (windowed and full layers, leading dense layers, the shared expert,
sigmoid routing, the experts held); the family's leaves go onto the
program's two stacks of layers (`dense_layers`, `layers`); and the
correctness sample also takes the requests still decoding when the window
closes, with the tokens they were served by then. Outputs of 512-4096
tokens at some 18 a second a row outlast a 51-s window, so few requests
finish in it, and a served token is compared the same whether or not its
request went on to finish.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict

from bench import weights as W
from bench.drivers import serve as S


def program_config(cfg: dict):
    """`serve.program_config` and the afmoe fields, checked against what
    the file says of each layer."""
    for key, want in (("model_type", "afmoe"), ("route_norm", True),
                      ("n_group", 1), ("topk_group", 1)):
        if cfg[key] != want:
            raise ValueError(f"{key}={cfg[key]!r}: the program serves {want!r}")
    d = cfg["hidden_size"]
    pcfg = S.program_config(cfg).replace(
        sliding_window=cfg["sliding_window"],
        full_attn_every=cfg["global_attn_every_n_layers"],
        embed_scale=math.sqrt(d) if cfg["mup_enabled"] else 1.0,
        moe=True, n_experts=cfg["router_experts"], experts_held=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"], d_ff_expert=cfg["moe_intermediate_size"],
        n_dense_layers=cfg["num_dense_layers"],
        n_shared_experts=cfg["num_shared_experts"],
        router_score=cfg["score_func"], route_scale=cfg["route_scale"])
    kinds = ["sliding_attention" if w else "full_attention" for w in pcfg.layer_windows]
    if kinds != list(cfg["layer_types"]):
        raise ValueError(f"layer_types {cfg['layer_types']} are not the program's {kinds}")
    return pcfg


def program_params(spec: W.Leaves, canon: Dict, model, n_dense: int):
    """The family's leaves in the program's tree: a global leaf at its
    dotted path, a per-layer leaf split between `dense_layers` (the first
    `n_dense`) and `layers`; checked leaf by leaf against the tree the
    program declares."""
    import jax

    tree: Dict = {}

    def put(path, arr):
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = arr

    for name, arr in canon.items():
        if name in spec.per_layer:
            put(("dense_layers",) + tuple(name.split(".")), arr[:n_dense])
            put(("layers",) + tuple(name.split(".")), arr[n_dense:])
        else:
            put(tuple(name.split(".")), arr)
    want = model.abstract_params()
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (w.shape, w.dtype) != (g.shape, g.dtype)
            for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the program's "
                         f"parameter tree:\nwant {want}\ngot {got}")
    return tree


class ServeDriver(S.ServeDriver):
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
                                build_model(pcfg), pcfg.n_dense_layers)
        jax.block_until_ready(params)
        self.engine = PagedServingEngine(
            pcfg, block_size=serve["block_size"],
            num_blocks=serve["num_blocks"], params=params,
            on_token=self._on_token, on_finish=self._on_finish)
        self.memory_after_build = jax.devices()[0].memory_stats() or {}
        self.traffic = S.T.build(self.mix, self.seed, self.cfg["vocab_size"],
                                 self._horizon())

    def run(self, t_process: float, compile_log) -> None:
        super().run(t_process, compile_log)
        self.cut = []
        for rec in self.recs.values():
            if rec.tag != "warm" and rec.state == "open":
                toks = self.engine.request(rec.rid).generated[:rec.target]
                if toks:
                    self.cut.append(dataclasses.replace(rec, tokens=list(toks)))

    def finished(self):
        """Requests that completed after set-up, and those still decoding
        at the window's close with their tokens so far."""
        return super().finished() + self.cut
