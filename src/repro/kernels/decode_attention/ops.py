"""Public flash-decode op with latency-aware depth selection.

``depth=None`` solves the pipeline depth from the KV-block `CoroSpec`
(`decode_attention.decode_spec`) via core.autotune — the VMEM cap comes
from the classified context bytes (shared online-softmax accumulators
don't multiply by depth), adaptive once transfer samples are recorded.
"""
from __future__ import annotations

from repro.core.machine import default_interpret
from repro.kernels.decode_attention.decode_attention import (
    flash_decode,
    paged_flash_decode,
)


def decode_attention(q, k_cache, v_cache, pos, *, blk: int = 128,
                     depth: int | None = None, interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    return flash_decode(q, k_cache, v_cache, pos, blk=blk, depth=depth,
                        interpret=interpret)


def paged_decode_attention(q, k_pools, v_pools, layer, block_tables, lengths,
                           *, window: int = 0, depth: int | None = None,
                           interpret: bool | None = None):
    """Ragged-batch decode over one layer of the stacked paged KV pools (see
    `decode_attention.paged_flash_decode`). ``depth=None`` solves the
    pipeline depth from the page-tile `CoroSpec` via core.autotune."""
    interpret = default_interpret() if interpret is None else interpret
    return paged_flash_decode(q, k_pools, v_pools, layer, block_tables,
                              lengths, window=window, depth=depth,
                              interpret=interpret)


# -------- fallback twins (core.guard degradation path, ISSUE-10) --------
from repro.kernels import register_twin  # noqa: E402


def _heads_unfolded(cache, d: int):
    """The kernel streams [..., KH*D]; the twins take [..., KH, D]."""
    return cache.reshape(*cache.shape[:-1], cache.shape[-1] // d, d)


def _flash_decode_twin(spec, pos, q, k_cache, v_cache):
    from repro.kernels.decode_attention.ref import decode_attention_ref
    d = q.shape[-1]
    return decode_attention_ref(q, _heads_unfolded(k_cache, d),
                                _heads_unfolded(v_cache, d), pos[0])


def _paged_decode_twin(spec, layer, bt_flat, lengths, lows, q, k_pools,
                       v_pools):
    # models.common.paged_decode_twin is the traceable masked twin the
    # serving engine runs off a TPU; the seed paged_decode_attention_ref is
    # a host loop (int(lengths[r])) and cannot police traced calls. The
    # kernel's window comes as each row's first attended position, `lows`.
    from repro.models.common import paged_decode_twin
    b = q.shape[0]
    out = paged_decode_twin(q[:, None], k_pools, v_pools, layer[0],
                            bt_flat.reshape(b, -1), lengths, lo=lows)
    return out[:, 0].astype(q.dtype)


register_twin("flash_decode", _flash_decode_twin)
register_twin("paged_decode", _paged_decode_twin)
