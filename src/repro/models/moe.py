"""Mixture-of-Experts layer: top-k router + two dispatch backends.

Dispatch is the paper's hash-join probe (CoroAMU Table II "HJ"): every token is
a tuple probing the expert "hash table". Two backends:

  * dense — mask-based einsum over all experts. Exact (dropless); used for
    reduced smoke configs and as the oracle.
  * ep    — expert-parallel: sort tokens by expert, capacity-bounded dispatch
    buffers, all_to_all over the `model` axis, local grouped matmul,
    all_to_all back, weighted combine. This is the collective-heavy path the
    roofline/§Perf work targets, and on TPU kernels/moe_gmm streams expert
    weights with decoupled DMA.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.params import ParamSpec
from repro.sharding import ShardingCtx


def moe_param_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    """The router spans every routed expert (`n_experts`); the expert
    weights are those held here (`n_experts_held`)."""
    e, dm, dff = cfg.n_experts_held, cfg.d_model, cfg.d_ff_expert
    s = {
        "router": ParamSpec((dm, cfg.n_experts), ("embed", "experts"), init="fan_in"),
        "w_gate": ParamSpec((e, dm, dff), ("experts", "embed", "mlp"), init="fan_in"),
        "w_up": ParamSpec((e, dm, dff), ("experts", "embed", "mlp"), init="fan_in"),
        "w_down": ParamSpec((e, dff, dm), ("experts", "mlp", "embed"), init="fan_in"),
    }
    if cfg.router_score == "sigmoid":
        # a selection bias (load balancing): it moves which experts are
        # chosen, never their weights
        s["expert_bias"] = ParamSpec((cfg.n_experts,), ("experts",), init="zeros")
    if cfg.n_shared_experts:
        f = cfg.n_shared_experts * dff
        s["shared"] = {
            "w_gate": ParamSpec((dm, f), ("embed", "mlp"), init="fan_in"),
            "w_up": ParamSpec((dm, f), ("embed", "mlp"), init="fan_in"),
            "w_down": ParamSpec((f, dm), ("mlp", "embed"), init="fan_in"),
        }
    return s


def router_topk(x, w_router, top_k: int, *, bias=None, scale: float = 1.0):
    """x:[T,d] -> (gates [T,k], experts [T,k] int32, aux_loss scalar).

    Softmax routing without `bias`. With `bias` (sigmoid routing): scores
    s = sigmoid(x W_r) in float32, experts chosen by top-k of s + bias,
    weighted by their s normalised over the k chosen, times `scale`."""
    logits = (x.astype(jnp.float32) @ w_router.astype(jnp.float32))
    if bias is not None:
        scores = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
        gates = scale * gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, experts = jax.lax.top_k(probs, top_k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # load-balancing aux loss (Switch-style)
    e = w_router.shape[1]
    me = probs.mean(0)
    one_hot = jax.nn.one_hot(experts[:, 0], e, dtype=jnp.float32)
    ce = one_hot.mean(0)
    aux = e * jnp.sum(me * ce)
    return gates.astype(x.dtype), experts.astype(jnp.int32), aux


GMM_F_TILE = 128


def _gmm_eligible(xs, wg, wu, wd) -> bool:
    """The streamed-weight kernel wants matched [E, ...] batching and tile-
    divisible output widths (dff and d_model for the down projection)."""
    return (xs.ndim == 3 and xs.shape[0] == wg.shape[0]
            and wg.shape[-1] % GMM_F_TILE == 0
            and wd.shape[-1] % GMM_F_TILE == 0)


@jax.custom_vjp
def _gmm(xs, w):
    """[E,T,d] x [E,d,f] -> [E,T,f] through the streamed-weight kernel.
    The kernel has no transpose rule, so the backward pass is the plain
    grouped einsum (what lets the EP training step run the kernel)."""
    from repro.kernels.moe_gmm.ops import moe_gmm
    return moe_gmm(xs, w, f_tile=GMM_F_TILE)


def _gmm_fwd(xs, w):
    return _gmm(xs, w), (xs, w)


def _gmm_bwd(res, g):
    xs, w = res
    return (jnp.einsum("etf,edf->etd", g, w),
            jnp.einsum("etd,etf->edf", xs, g))


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _expert_ffn(xs, wg, wu, wd, *, use_gmm: bool | None = None):
    """Per-expert SwiGLU. On TPU (when shapes allow) each grouped matmul is
    the `kernels/moe_gmm` coroutine pipeline — expert weights are the far-
    memory objects, streamed HBM->VMEM tile-by-tile while the MXU consumes
    the previous tile. The dense einsum below is the jnp twin, kept as the
    interpret-mode / CPU fallback (ROADMAP: MoE expert-parallel dispatch)."""
    if use_gmm is None:
        from repro.core.machine import default_interpret
        use_gmm = not default_interpret()
    if use_gmm and _gmm_eligible(xs, wg, wu, wd):
        h = jax.nn.silu(_gmm(xs, wg.astype(xs.dtype)))
        h = h * _gmm(xs, wu.astype(xs.dtype))
        return _gmm(h, wd.astype(xs.dtype))
    h = jax.nn.silu(jnp.einsum("...td,...df->...tf", xs, wg.astype(xs.dtype)))
    h = h * jnp.einsum("...td,...df->...tf", xs, wu.astype(xs.dtype))
    return jnp.einsum("...tf,...fd->...td", h, wd.astype(xs.dtype))


def shared_expert(p, x):
    """The shared experts' SwiGLU, which every token passes through."""
    h = jax.nn.silu(x @ p["w_gate"].astype(x.dtype)) * (x @ p["w_up"].astype(x.dtype))
    return h @ p["w_down"].astype(x.dtype)


def moe_dense(p, x, cfg: ArchConfig, *, first: int = 0):
    """Oracle/dense backend: computes every held expert for every token via
    masks.

    x: [B,S,d]. Routes over all `cfg.n_experts` and combines, dropless,
    the part of the result the held experts (ids `first` onwards, as many
    as `p["w_gate"]` holds) give, plus the shared experts' where the layer
    has them. O(T * E_held * d * dff) flops.
    """
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    with jax.named_scope("router"):
        gates, experts, aux = router_topk(xt, p["router"], cfg.top_k,
                                          bias=p.get("expert_bias"),
                                          scale=cfg.route_scale)
    with jax.named_scope("experts"):
        outs = _expert_ffn(xt[None], p["w_gate"], p["w_up"], p["w_down"])
        # experts not held here one-hot to a zero row
        held = experts - first if first else experts
        comb = (jax.nn.one_hot(held, p["w_gate"].shape[0], dtype=xt.dtype)
                * gates[..., None])
        y = jnp.einsum("tke,etd->td", comb, outs)     # outs [E,T,d]
    if "shared" in p:
        with jax.named_scope("shared_expert"):
            y = y + shared_expert(p["shared"], xt)
    return y.reshape(b, s, d), aux


def _capacity(tokens: int, cfg: ArchConfig) -> int:
    cap = int(tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, (cap + 7) // 8 * 8)


def _dispatch_local(xt, gates, experts, cfg: ArchConfig, capacity: int):
    """Sort-based capacity dispatch. xt:[T,d] -> buf [E,C,d] + combine meta."""
    t, d = xt.shape
    k = cfg.top_k
    e = cfg.n_experts
    flat_e = experts.reshape(-1)                     # [T*k]
    flat_t = jnp.repeat(jnp.arange(t), k)            # token id per assignment
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e)                      # group by expert
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    # position within expert group
    ones = jnp.ones_like(se)
    pos_in_e = jax.lax.associative_scan(jnp.add, ones) - 1
    seg_start = jnp.searchsorted(se, jnp.arange(e), side="left")
    pos_in_e = pos_in_e - seg_start[se]
    keep = pos_in_e < capacity
    slot = se * capacity + jnp.where(keep, pos_in_e, 0)
    buf = jnp.zeros((e * capacity, d), xt.dtype)
    buf = buf.at[slot].add(jnp.where(keep[:, None], xt[st], 0))
    meta = dict(slot=slot, token=st, gate=sg, keep=keep)
    return buf.reshape(e, capacity, d), meta


def _combine_local(buf, meta, t: int):
    """buf [E,C,d] -> y [T,d] weighted by gates."""
    e, c, d = buf.shape
    flat = buf.reshape(e * c, d)
    contrib = flat[meta["slot"]] * meta["gate"][:, None] * meta["keep"][:, None]
    y = jnp.zeros((t, d), buf.dtype).at[meta["token"]].add(contrib)
    return y


def _mesh_bspec(ctx: ShardingCtx):
    dp = tuple(a for a in ctx.mesh.axis_names if a in ("pod", "data"))
    return dp if len(dp) > 1 else dp[0]


def _expert_specs():
    return {
        "router": P(None, None),
        "w_gate": P("model", None, None),
        "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }


def moe_ep_a2a(p, x, cfg: ArchConfig, ctx: ShardingCtx):
    """SP+EP backend: tokens sequence-sharded over `model`, experts sharded
    over `model`; two all_to_all exchanges move tokens to/from expert owners.

    Per-shard: tokens [T_l, d] -> dispatch [E,C,d] -> all_to_all over model
    -> local experts [E_l, n_model*C, d] -> ffn -> all_to_all back -> combine.
    """
    mesh = ctx.mesh
    n_model = ctx.axis_sizes["model"]
    bspec = _mesh_bspec(ctx)
    b, s, d = x.shape
    all_axes = tuple(mesh.axis_names)

    def fn(p_l, x_l):
        bl, sl, _ = x_l.shape
        xt = x_l.reshape(bl * sl, d)
        gates, experts, aux = router_topk(xt, p_l["router"], cfg.top_k)
        cap = _capacity(xt.shape[0], cfg)
        buf, meta = _dispatch_local(xt, gates, experts, cfg, cap)   # [E,C,d]
        e, c, _ = buf.shape
        e_l = e // n_model
        buf = buf.reshape(n_model, e_l, c, d)
        recv = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0)
        # recv[src, e_l, c, d]: capacity blocks from every source shard
        recv = recv.swapaxes(0, 1).reshape(e_l, n_model * c, d)
        out = _expert_ffn(recv, p_l["w_gate"], p_l["w_up"], p_l["w_down"])
        out = out.reshape(e_l, n_model, c, d).swapaxes(0, 1)
        back = jax.lax.all_to_all(out, "model", split_axis=0, concat_axis=0)
        y = _combine_local(back.reshape(e, c, d), meta, xt.shape[0])
        aux = jax.lax.pmean(aux, all_axes)
        return y.reshape(bl, sl, d), aux

    in_x = P(bspec, "model", None)  # sequence-parallel tokens
    y, aux = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(_expert_specs(), in_x),
        out_specs=(in_x, P()),
        check_vma=False,
    )(p, x)
    return y, aux


def moe_ep_replicated(p, x, cfg: ArchConfig, ctx: ShardingCtx):
    """EP for tokens replicated over `model` (decode: seq=1). Each shard
    computes its local experts for all its tokens; psum combines over model."""
    mesh = ctx.mesh
    n_model = ctx.axis_sizes["model"]
    bspec = _mesh_bspec(ctx)
    b, s, d = x.shape
    e_l = cfg.n_experts // n_model
    all_axes = tuple(mesh.axis_names)

    def fn(p_l, x_l):
        bl, sl, _ = x_l.shape
        xt = x_l.reshape(bl * sl, d)
        gates, experts, aux = router_topk(xt, p_l["router"], cfg.top_k)
        e0 = jax.lax.axis_index("model") * e_l
        rel = experts - e0
        local = (rel >= 0) & (rel < e_l)
        outs = _expert_ffn(xt[None], p_l["w_gate"], p_l["w_up"], p_l["w_down"])
        comb = jax.nn.one_hot(jnp.where(local, rel, 0), e_l, dtype=xt.dtype)
        comb = comb * (gates * local.astype(gates.dtype))[..., None]
        y = jnp.einsum("tke,etd->td", comb, outs)
        y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, all_axes)
        return y.reshape(bl, sl, d), aux

    in_x = P(bspec, None, None)
    y, aux = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(_expert_specs(), in_x),
        out_specs=(in_x, P()),
        check_vma=False,
    )(p, x)
    return y, aux


def moe_layer(p, x, cfg: ArchConfig, ctx: ShardingCtx):
    impl = cfg.moe_impl
    n_model = ctx.axis_sizes.get("model", 0) if ctx.mesh is not None else 0
    ep_ok = (
        ctx.mesh is not None and ctx.use_shard_map and n_model
        and cfg.n_experts % n_model == 0
    )
    if impl == "auto":
        impl = "ep" if ep_ok else "dense"
    if impl == "ep" and ep_ok:
        if x.shape[1] % n_model == 0:
            return moe_ep_a2a(p, x, cfg, ctx)
        return moe_ep_replicated(p, x, cfg, ctx)
    return moe_dense(p, x, cfg)
