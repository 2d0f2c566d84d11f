"""Public SSD-scan op.

``depth=None`` solves the number of in-flight chunk loads from the
declared `CoroSpec` (`ssd_scan.ssd_spec`) via core.autotune — the
sequential recurrent state is one copy regardless of depth, so it caps
the budget once, not per slot.
"""
from __future__ import annotations

from repro.core.machine import default_interpret
from repro.kernels.ssd_scan.ssd_scan import ssd_scan


def ssd(x, dt, A, B, C, *, chunk: int = 64, depth: int | None = None,
        interpret: bool | None = None):
    """Batched SSD. x:[b,s,nh,p] dt:[b,s,nh] A:[nh] B,C:[b,s,n]."""
    interpret = default_interpret() if interpret is None else interpret
    return ssd_scan(x, dt, A, B, C, chunk=chunk, depth=depth,
                    interpret=interpret)


# -------- fallback twin (core.guard degradation path, ISSUE-10) --------
from repro.kernels import register_twin  # noqa: E402


def _ssd_twin(spec, x, dt, A, B, C):
    # same chunking as the kernel (spec.loads[0] is the x chunk stream), so
    # the parity sentinel compares like-for-like chunked math; the kernel's
    # operands carry x as [b, s, H*P] and dt / A lane-padded past H
    import jax.numpy as jnp

    from repro.models.ssm import ssd_chunked
    chunk = spec.loads[0].tile[0]
    nh, p, _ = spec.vars[0].shape
    b, s, _ = dt.shape
    y, h_final = ssd_chunked(x.reshape(b, s, nh, p), dt[..., :nh],
                             A[0, :nh], B, C, chunk)
    return [y.reshape(x.shape).astype(x.dtype), h_final.astype(jnp.float32)]


register_twin("ssd_scan", _ssd_twin)
