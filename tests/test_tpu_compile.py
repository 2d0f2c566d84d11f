"""Real-width compiles for a described TPU v5e (no chip needed).

Each kernel of the main path is lowered with ``interpret=False`` and
compiled by the TPU compiler against a described ``v5e:2x2`` topology, at
the widths it serves: the decode kernels at head_dim 64 (granite-3-2b) and
128 (yi-6b), ``ssd_scan`` at mamba2-130m, ``moe_gmm`` at
granite-moe-1b-a400m and ``stream_triad`` at its solved depth. The
granite-3-2b paged decode step is compiled whole, and granite-moe-1b-a400m's
and trinity-mini's decode and prefill programs at the benchmark's pool sizes. Nothing runs, so these
tests say nothing about results or speed; they catch what Mosaic or XLA
would refuse on the chip (misaligned tiles, missing lowerings, VMEM or HBM
overcommit).

The topology is described inside a fixture: only one process may load the
TPU library at a time, so it must never happen while modules are imported.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

PALLAS_CALL = "tpu_custom_call"
HBM_BYTES = 16 * 10**9          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


@pytest.fixture(scope="module")
def shape(one_chip):
    def make(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


@pytest.mark.parametrize("kind", ["paged", "flash"])
@pytest.mark.parametrize("kh,d", [(8, 64), (4, 128)],
                         ids=["granite-3-2b", "yi-6b"])
def test_decode_kernels_compile(shape, kind, kh, d):
    from repro.kernels.decode_attention.decode_attention import (
        flash_decode,
        paged_flash_decode,
    )
    bf16 = jnp.bfloat16
    q = shape((8, 32, d), bf16)
    if kind == "paged":
        pools = shape((4, 1024, 16, kh * d), bf16)
        compiled = _compile(
            lambda q, k, v, layer, bt, ln: paged_flash_decode(
                q, k, v, layer, bt, ln, interpret=False),
            q, pools, pools, shape((), jnp.int32), shape((8, 64), jnp.int32),
            shape((8,), jnp.int32))
    else:
        cache = shape((8, 2048, kh, d), bf16)
        compiled = _compile(
            lambda q, k, v: flash_decode(q, k, v, 1000, interpret=False),
            q, cache, cache)
    assert PALLAS_CALL in compiled.as_text()


def test_ssd_scan_compiles_at_mamba2_130m(shape):
    from repro.kernels.ssd_scan.ssd_scan import ssd_scan
    f32 = jnp.float32
    compiled = _compile(
        lambda x, dt, a, b, c: ssd_scan(x, dt, a, b, c, chunk=256,
                                        interpret=False),
        shape((2, 1024, 24, 64), f32), shape((2, 1024, 24), f32),
        shape((24,), f32), shape((2, 1024, 128), f32),
        shape((2, 1024, 128), f32))
    assert PALLAS_CALL in compiled.as_text()


def test_stream_triad_compiles_at_solved_depth(shape):
    """rows=512 over f32 [65536, 1024] solves a depth whose scratch exceeds
    Mosaic's 16 MiB default: it compiles only under the solver's budget."""
    from repro.core import autotune
    from repro.kernels.stream_copy.stream_copy import triad, triad_spec
    spec = triad_spec(512, 1024, jnp.float32)
    depth = autotune.choose_depth(spec.profile(), vars=spec.all_vars())
    assert spec.context_bytes(depth) > 16 * 2**20
    arr = shape((65536, 1024), jnp.float32)
    compiled = _compile(
        lambda b, c: triad(b, c, 3.0, rows=512, depth=depth, interpret=False),
        arr, arr)
    assert PALLAS_CALL in compiled.as_text()


def test_moe_gmm_compiles_at_granite_moe(shape):
    from repro.kernels.moe_gmm.moe_gmm import gmm
    compiled = _compile(lambda t, w: gmm(t, w, interpret=False),
                        shape((32, 128, 1024), jnp.bfloat16),
                        shape((32, 1024, 512), jnp.bfloat16))
    assert PALLAS_CALL in compiled.as_text()


def test_granite_3_2b_paged_decode_step_fits_one_chip(one_chip, shape):
    """The served decode step at published widths (bf16 weights, 8 slots,
    table width 64, 1024 blocks of 16) compiles and fits one chip's HBM."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.runtime.layouts import serving_config_overrides
    from repro.serve.engine import decode_round_fn, kv_pool_shape

    cfg = get_config("granite-3-2b").replace(**serving_config_overrides())
    model = build_model(cfg)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        model.abstract_params())
    pool = shape(kv_pool_shape(cfg, 1025, 16), jnp.dtype(cfg.dtype))
    compiled = jax.jit(decode_round_fn(model), donate_argnums=(1, 2)).lower(
        params, pool, pool, shape((8, 1), jnp.int32),
        shape((8, 64), jnp.int32), shape((8,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < total < HBM_BYTES


_HLO_RESULT = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")
_POOL_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


@pytest.fixture
def decode_kernel_on(monkeypatch):
    """Select the Pallas decode kernel as on a TPU: the platform check says
    "not interpreted" (the compiles here target a described chip)."""
    from repro.core import machine
    monkeypatch.setattr(machine, "default_interpret", lambda: False)


def _results_of_shape(hlo_text: str, dims) -> list:
    """Instructions whose result has the shape `dims`."""
    want = ",".join(map(str, dims))
    return [line.strip()[:160] for line in hlo_text.splitlines()
            if (m := _HLO_RESULT.match(line)) and m.group(2) == want]


def _assert_decode_reads_pages_in_place(text: str, rows: int, width: int,
                                        kh: int, d: int, blk: int = 16):
    """The decode program holds the kernel, and neither the gathered pages
    ([rows * width, blk, KH*D]) nor their head unfold
    ([rows, width * blk, KH, D])."""
    assert PALLAS_CALL in text
    assert _results_of_shape(text, (rows * width, blk, kh * d)) == []
    assert _results_of_shape(text, (rows, width * blk, kh, d)) == []


def _pool_sized_moves(hlo_text: str, layer_elems: int):
    """Instructions that copy or slice at least one layer's pool: a `copy`,
    `dynamic-slice` or `dynamic-update-slice` (or a fusion named for one)
    whose result holds ``layer_elems`` elements or more."""
    out = []
    for line in hlo_text.splitlines():
        m = _HLO_RESULT.match(line)
        if not m:
            continue
        name, dims, opcode = m.groups()
        n = 1
        for dim in filter(None, dims.split(",")):
            n *= int(dim)
        if n >= layer_elems and (opcode in _POOL_MOVES or any(
                f"{op}_fusion" in name for op in _POOL_MOVES[1:])):
            out.append(line.strip()[:160])
    return out


@pytest.mark.parametrize("program", ["paged_decode_round",
                                     "paged_prefill_chunk"])
def test_granite_moe_step_programs_update_the_pools_in_place(
        one_chip, shape, program, decode_kernel_on):
    """granite-moe-1b-a400m's step programs at the benchmark's sizes (7000
    blocks of 16 and the garbage page; a decode round of 20 rows or a
    prefill chunk of 32, table width 192), with the Pallas decode kernel
    selected, update the donated pools in place: both pools alias outputs,
    temporaries stay under 1 GB (the decode round's under 20 MB), and no
    instruction copies or slices a pool-sized array. The decode round reads
    the pages through the kernel: no page gather, no head unfold."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.runtime.layouts import serving_config_overrides
    from repro.serve.engine import decode_round_fn, kv_pool_shape
    from repro.serve.prefill import ChunkedPrefiller

    cfg = get_config("granite-moe-1b-a400m").replace(
        dtype="bfloat16", **serving_config_overrides())
    model = build_model(cfg)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        model.abstract_params())
    dims = kv_pool_shape(cfg, 7001, 16)
    pool = shape(dims, jnp.bfloat16)
    i32 = jnp.int32
    if program == "paged_decode_round":
        fn = jax.jit(decode_round_fn(model), donate_argnums=(1, 2))
        args = (shape((20, 1), i32), shape((20, 192), i32), shape((20,), i32))
    else:
        fn = ChunkedPrefiller(model, 16, tracer=None)._fn(32, 192)
        args = (shape((1, 32), i32), shape((1, 192), i32), shape((), i32),
                shape((), i32))
    compiled = fn.lower(params, pool, pool, *args).compile()
    mem = compiled.memory_analysis()
    pool_bytes = pool.size * pool.dtype.itemsize
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < 10**9
    layer_elems = pool.size // dims[0]
    text = compiled.as_text()
    assert _pool_sized_moves(text, layer_elems) == []
    if program == "paged_decode_round":
        assert mem.temp_size_in_bytes < 20 * 10**6
        _assert_decode_reads_pages_in_place(text, 20, 192, 8, 64)


@pytest.mark.parametrize("program", ["paged_decode_round",
                                     "paged_prefill_chunk"])
def test_trinity_mini_step_programs_update_the_pools_in_place(
        one_chip, shape, program, decode_kernel_on):
    """trinity-mini as the benchmark serves it (16 layers, 12 windowed and 4
    full; 2 dense then 14 expert layers holding 16 of 128 experts; 6144
    blocks of 16 and the garbage page; a decode round of 20 rows or a
    prefill chunk of 32, table width 512), with the Pallas decode kernel
    selected: both pools alias outputs, temporaries stay under 1 GB (the
    decode round's under 20 MB), and no instruction copies or slices an
    array of a pool's shape or of one layer of it. (The prefill chunk's
    gathered pages outnumber one layer's pool at this width, so the check
    goes by shape, not by size.) The decode round reads the pages through
    the kernel: no page gather, full ([20, 512, ...]) or windowed
    ([20, 129, ...]), and no head unfold."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.runtime.layouts import serving_config_overrides
    from repro.serve.engine import decode_round_fn, kv_pool_shape
    from repro.serve.prefill import ChunkedPrefiller

    cfg = get_config("trinity-mini").replace(
        n_layers=16, experts_held=16, dtype="bfloat16",
        **serving_config_overrides())
    model = build_model(cfg)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        model.abstract_params())
    dims = kv_pool_shape(cfg, 6145, 16)
    pool = shape(dims, jnp.bfloat16)
    i32 = jnp.int32
    if program == "paged_decode_round":
        fn = jax.jit(decode_round_fn(model), donate_argnums=(1, 2))
        args = (shape((20, 1), i32), shape((20, 512), i32), shape((20,), i32))
    else:
        fn = ChunkedPrefiller(model, 16, tracer=None)._fn(32, 512)
        args = (shape((1, 32), i32), shape((1, 512), i32), shape((), i32),
                shape((), i32))
    compiled = fn.lower(params, pool, pool, *args).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool.size * pool.dtype.itemsize
    assert mem.temp_size_in_bytes < 10**9
    pool_shapes = {",".join(map(str, dims)), ",".join(map(str, dims[1:]))}
    text = compiled.as_text()
    moves = _pool_sized_moves(text, pool.size // dims[0])
    assert [m for m in moves if _HLO_RESULT.match(m).group(2) in pool_shapes] == []
    if program == "paged_decode_round":
        assert mem.temp_size_in_bytes < 20 * 10**6
        for width in (512, 129):
            _assert_decode_reads_pages_in_place(text, 20, width, 4, 128)
