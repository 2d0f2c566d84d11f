"""Per-arch smoke tests: reduced config, one train step + prefill/decode on CPU.

Full configs are exercised only via the dry-run (ShapeDtypeStruct, no
allocation) — see launch/dryrun.py and tests/test_dryrun_mini.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCH_NAMES, REGISTRY, token_split
from repro.models import build_model
from repro.optim import AdamWConfig, init_state
from repro.runtime.steps import make_train_step

# the afmoe block (trinity-mini) runs through the paged serving paths only
DENSE_ARCHS = tuple(a for a in ALL_ARCH_NAMES if not REGISTRY[a].paged_only)
PAGED_ONLY = tuple(a for a in ALL_ARCH_NAMES if REGISTRY[a].paged_only)


def _batch(cfg, b, s, rng):
    front, text = token_split(cfg, s)
    batch = {
        "tokens": jnp.asarray(rng.randint(0, cfg.vocab, (b, text)), jnp.int32),
        "targets": jnp.asarray(rng.randint(0, cfg.vocab, (b, text)), jnp.int32),
        "positions": jnp.tile(jnp.arange(text, dtype=jnp.int32), (b, 1)),
    }
    if cfg.enc_dec:
        batch["frames"] = jnp.asarray(rng.randn(b, front, cfg.d_model) * 0.02, jnp.float32)
    if cfg.vlm:
        batch["patches"] = jnp.asarray(rng.randn(b, front, cfg.d_model) * 0.02, jnp.float32)
    return batch, text


@pytest.mark.parametrize("arch", DENSE_ARCHS)
def test_arch_train_step(arch, rng):
    cfg = REGISTRY[arch].reduced()
    model = build_model(cfg)
    state = init_state(model.init(jax.random.PRNGKey(0)))
    batch, _ = _batch(cfg, 2, 32, rng)
    step = jax.jit(make_train_step(model, AdamWConfig(total_steps=10)))
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_state["step"]) == 1
    # params actually moved
    delta = sum(float(jnp.abs(a - b).sum()) for a, b in zip(
        jax.tree.leaves(state["params"]), jax.tree.leaves(new_state["params"])))
    assert delta > 0.0


@pytest.mark.parametrize("arch", DENSE_ARCHS)
def test_arch_prefill_decode_shapes(arch, rng):
    cfg = REGISTRY[arch].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch, text = _batch(cfg, 2, 32, rng)
    cache, logits = model.prefill(params, batch, pad_to=text + 4)
    assert logits.shape == (2, 1, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()
    dbatch = {"tokens": jnp.ones((2, 1), jnp.int32),
              "positions": jnp.full((2, 1), text, jnp.int32)}
    logits2, cache2 = model.decode_step(params, cache, dbatch)
    assert logits2.shape == (2, 1, cfg.vocab)
    assert np.isfinite(np.asarray(logits2)).all()
    assert int(cache2["pos"]) == int(cache["pos"]) + 1


@pytest.mark.parametrize("arch", ["granite-3-2b", "qwen3-moe-30b-a3b", "mamba2-130m",
                                  "hymba-1.5b", "whisper-medium", "paligemma-3b"])
def test_decode_matches_full_forward(arch, rng):
    """prefill(prompt[:-1]) + decode(last) == prefill(prompt) last logits."""
    cfg = REGISTRY[arch].reduced().replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    batch, text = _batch(cfg, 2, 33, rng)
    _, logits_full = model.prefill(params, batch)
    short = dict(batch)
    short["tokens"] = batch["tokens"][:, :-1]
    short["targets"] = batch["targets"][:, :-1]
    short["positions"] = batch["positions"][:, :-1]
    cache, _ = model.prefill(params, short, pad_to=text + 4)
    dbatch = {"tokens": batch["tokens"][:, -1:],
              "positions": jnp.full((2, 1), text - 1, jnp.int32)}
    logits_dec, _ = model.decode_step(params, cache, dbatch)
    rel = float(jnp.abs(logits_full - logits_dec).max()) / float(jnp.abs(logits_full).max())
    assert rel < 2e-4, f"{arch}: decode/full mismatch rel={rel}"


@pytest.mark.parametrize("arch", PAGED_ONLY)
def test_paged_only_arch_serves_and_refuses_the_dense_path(arch, rng):
    """A reduced paged-only config (windowed and full layers, a leading
    dense layer, the expert share) serves through the paged engine; the
    unpaged entry points refuse it rather than compute another model."""
    from repro.serve import PagedServingEngine

    cfg = REGISTRY[arch].reduced()
    assert set(cfg.layer_windows) == {0, cfg.sliding_window}
    eng = PagedServingEngine(cfg, block_size=8, num_blocks=64, max_in_flight=2)
    for n in (40, 9):
        eng.submit(rng.randint(0, cfg.vocab, n).tolist(), max_new_tokens=6)
    stats = eng.run()
    assert stats["completed"] == 2 and stats["failed"] == 0
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch, _ = _batch(cfg, 2, 32, rng)
    with pytest.raises(NotImplementedError, match="paged"):
        model.loss(params, batch)
    with pytest.raises(NotImplementedError, match="paged"):
        model.prefill(params, batch)


def test_mamba2_split_proj_trains(rng):
    """§Perf shard-aligned SSD layout: same family, different param layout."""
    import jax
    import jax.numpy as jnp
    from repro.optim import AdamWConfig, init_state
    from repro.runtime.steps import make_train_step
    cfg = REGISTRY["mamba2-130m"].reduced().replace(ssm_split_proj=True)
    model = build_model(cfg)
    state = init_state(model.init(jax.random.PRNGKey(0)))
    batch, _ = _batch(cfg, 2, 32, rng)
    st, metrics = jax.jit(make_train_step(model, AdamWConfig(total_steps=5)))(state, batch)
    assert np.isfinite(float(metrics["loss"]))
