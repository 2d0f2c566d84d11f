"""Grouped-matmul kernel vs einsum oracle; MoE layer backends agree."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.moe_gmm.ops import moe_gmm
from repro.kernels.moe_gmm.ref import gmm_ref
from repro.models.moe import (
    _expert_ffn,
    _gmm_eligible,
    moe_dense,
    moe_param_specs,
    router_topk,
)
from repro.models import params as pm


@pytest.mark.parametrize("e,c,dm,f,ft", [(2, 8, 16, 128, 128), (4, 16, 32, 256, 128)])
def test_gmm_matches_ref(rng, e, c, dm, f, ft):
    t = jnp.asarray(rng.randn(e, c, dm), jnp.float32)
    w = jnp.asarray(rng.randn(e, dm, f), jnp.float32)
    np.testing.assert_allclose(np.asarray(moe_gmm(t, w, f_tile=ft)),
                               np.asarray(gmm_ref(t, w)), rtol=1e-4, atol=1e-4)


def test_router_topk_normalized(rng):
    cfg = get_config("qwen3-moe-30b-a3b").reduced()
    p = pm.initialize(jax.random.PRNGKey(0), moe_param_specs(cfg))
    x = jnp.asarray(rng.randn(32, cfg.d_model), jnp.float32)
    gates, experts, aux = router_topk(x, p["router"], cfg.top_k)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-5)
    assert int(experts.max()) < cfg.n_experts
    assert float(aux) > 0.0


def test_expert_ffn_gmm_backend_matches_dense(rng):
    """The streamed-weight gmm backend (TPU dispatch path, run here in
    interpret mode) must agree with the jnp-einsum twin."""
    e, c, dm, f = 2, 8, 128, 256
    xs = jnp.asarray(rng.randn(e, c, dm), jnp.float32)
    wg = jnp.asarray(rng.randn(e, dm, f) * 0.1, jnp.float32)
    wu = jnp.asarray(rng.randn(e, dm, f) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.randn(e, f, dm) * 0.1, jnp.float32)
    got = _expert_ffn(xs, wg, wu, wd, use_gmm=True)
    want = _expert_ffn(xs, wg, wu, wd, use_gmm=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_expert_ffn_gmm_gating(rng):
    """Shapes the kernel can't tile (or mismatched expert batching) fall
    back to the einsum twin instead of asserting inside the kernel."""
    e, c, dm, f = 2, 4, 96, 96  # not divisible by the 128 f_tile
    xs = jnp.asarray(rng.randn(e, c, dm), jnp.float32)
    wg = jnp.asarray(rng.randn(e, dm, f), jnp.float32)
    wu = jnp.asarray(rng.randn(e, dm, f), jnp.float32)
    wd = jnp.asarray(rng.randn(e, f, dm), jnp.float32)
    assert not _gmm_eligible(xs, wg, wu, wd)
    assert not _gmm_eligible(xs[:1], jnp.zeros((4, dm, 128)),
                             jnp.zeros((4, dm, 128)), jnp.zeros((4, 128, dm)))
    out = _expert_ffn(xs, wg, wu, wd, use_gmm=True)  # falls back, no raise
    assert out.shape == (e, c, dm)


def test_moe_dense_combines_topk_only(rng):
    cfg = get_config("granite-moe-1b-a400m").reduced()
    p = pm.initialize(jax.random.PRNGKey(1), moe_param_specs(cfg))
    x = jnp.asarray(rng.randn(2, 8, cfg.d_model), jnp.float32)
    y, aux = moe_dense(p, x, cfg)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()


def test_gmm_expert_ffn_trains_through_the_kernel(rng):
    """The kernel path has a backward pass (the grouped einsum): gradients
    through `_expert_ffn(use_gmm=True)` match the einsum twin's."""
    e, t, dm, dff = 2, 8, 128, 128
    args = [jnp.asarray(rng.randn(*s) * 0.1, jnp.float32)
            for s in ((e, t, dm), (e, dm, dff), (e, dm, dff), (e, dff, dm))]

    def grads(use_gmm):
        loss = lambda *a: (_expert_ffn(*a, use_gmm=use_gmm) ** 2).sum()
        return jax.grad(loss, argnums=(0, 1, 2, 3))(*args)

    for got, want in zip(grads(True), grads(False)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-4)
