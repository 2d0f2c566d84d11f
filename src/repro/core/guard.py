"""Guarded execution for the coroutine kernel substrate (DESIGN.md §2.7).

ISSUE-9 made the serving engine crash-proof; this module gives the layer
underneath it — every `coro_call` pipeline — a defined completion/failure
contract: a guarded call either returns a correct result or degrades
through a declared ladder, never an unhandled exception and never silent
wrong numbers. The pieces:

* **Error taxonomy** — `SubstrateError` (kernel name, machine profile,
  depth, tile shape) with four concrete classes: `KernelCompileError`
  (Mosaic/lowering failures), `KernelResourceError` (RESOURCE_EXHAUSTED /
  VMEM overcommit), `KernelNumericsError` (non-finite outputs), and
  `KernelParityError` (sentinel mismatch vs the jnp twin).
* **Depth-backoff ladder** — a failed attempt at depth d is retried at
  `max(1, d // 2)`, re-deriving scratch shapes each step (the caller's
  `attempt(d)` closure rebuilds the pallas_call), until depth 1 fails too.
* **Twin fallback** — on ladder exhaustion the kernel family's registered
  jnp twin (`repro.kernels.fallback_twin`) computes the answer instead.
* **Circuit breaker** — per (machine, kernel): closed → open after
  `BREAKER_THRESHOLD` consecutive failures → half-open probe after
  `BREAKER_COOLDOWN_CALLS` guarded calls → closed on probe success. While
  open, calls route straight to the twin without attempting the kernel.
* **Config quarantine** — every failed (machine, kernel, depth) is pushed
  into `core.autotune`'s quarantine set so `choose_depth` never re-proposes
  a depth that just failed.
* **Parity sentinel** — opt-in (`REPRO_PARITY`: ``off`` | ``sampled`` |
  ``full``): a deterministic 1-in-N sample of guarded calls is re-run
  through the twin and compared within tolerance; a mismatch returns the
  twin's output and trips the same quarantine/breaker path. Always on,
  regardless of mode: a cheap NaN/Inf scan of every concrete output.
* **Strict mode** — `set_strict(True)` (serve.py/kernel_bench ``--strict``)
  disables every degradation: the first failure raises its typed error.

Every backoff, fallback, breaker transition, and parity mismatch emits an
`obs` trace instant plus counters (`substrate.backoffs`,
`substrate.fallbacks`, `substrate.parity_mismatches`, a breaker-state
gauge). `stats()` reports plain-int totals that survive
``REPRO_TELEMETRY=0``.

Fault injection: `set_injector` installs a `serve.faults`-style injector
whose ``kernel_compile`` / ``kernel_oom`` / ``kernel_nan`` streams fire
inside `guarded_call`; `check_injected` raises the same typed errors at
engine call sites (useful where pool donation forbids failing mid-call).
This module must not import `serve.faults` at module scope (serve imports
kernels imports core.coro imports this) — the null injector is local.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "BREAKER_COOLDOWN_CALLS",
    "BREAKER_THRESHOLD",
    "GuardResult",
    "KernelCompileError",
    "KernelNumericsError",
    "KernelParityError",
    "KernelResourceError",
    "SubstrateError",
    "breaker_state",
    "check_injected",
    "guarded_call",
    "last_ladder",
    "numerics_fault",
    "parity_matches",
    "parity_mode",
    "reset",
    "scan_output",
    "set_injector",
    "set_parity",
    "set_strict",
    "stats",
    "strict_mode",
]

PARITY_ENV = "REPRO_PARITY"            # off | sampled | full
PARITY_EVERY_ENV = "REPRO_PARITY_EVERY"
DEFAULT_PARITY_EVERY = 4               # sampled mode checks call 1, N+1, ...

BREAKER_THRESHOLD = 3                  # consecutive failures -> open
BREAKER_COOLDOWN_CALLS = 8             # open calls before a half-open probe

# substrings that mark a failure as resource pressure rather than a
# compile/lowering bug (jax surfaces TPU OOM as RESOURCE_EXHAUSTED; Mosaic
# VMEM overcommit mentions vmem/scoped memory)
_RESOURCE_MARKERS = ("resource_exhausted", "resource exhausted",
                     "out of memory", "vmem", "scoped vmem", "smem")


# ---------------------------------------------------------------- taxonomy


class SubstrateError(RuntimeError):
    """A kernel-substrate failure with its launch context attached.

    Subclass of RuntimeError so seed-era supervisors whose retriable set is
    ``(RuntimeError, OSError)`` (`runtime.fault_tolerance`) treat substrate
    faults as retriable without being taught the new taxonomy.
    """

    def __init__(self, message: str, *, kernel: str = "?",
                 machine: Optional[str] = None, depth: Optional[int] = None,
                 tile: Optional[Tuple[int, ...]] = None,
                 injected: bool = False):
        if machine is None:
            machine = _machine_name()
        super().__init__(
            f"{message} [kernel={kernel} machine={machine} depth={depth} "
            f"tile={tile}]")
        self.kernel = kernel
        self.machine = machine
        self.depth = depth
        self.tile = tile
        self.injected = injected   # raised by the fault injector on purpose


class KernelCompileError(SubstrateError):
    """Mosaic/lowering/launch failure (or an injected stand-in)."""


class KernelResourceError(SubstrateError):
    """RESOURCE_EXHAUSTED / VMEM overcommit at the attempted depth."""


class KernelNumericsError(SubstrateError):
    """Non-finite values in a kernel's output (the always-on scan)."""


class KernelParityError(SubstrateError):
    """Sentinel mismatch: kernel output diverged from the jnp twin."""


def _machine_name() -> str:
    try:
        from repro.core.machine import get_machine
        return get_machine().name
    except Exception:  # pragma: no cover - machine layer must not gate errors
        return "?"


# ------------------------------------------------------------ module state


class _NullInjector:
    """Default injector: never fires. serve.faults.NULL_INJECTOR has the
    same surface, but importing it here would close an import cycle."""

    __slots__ = ()

    def fire(self, site: str, **ctx: Any) -> bool:
        return False


_NULL_INJECTOR = _NullInjector()

_COUNT_KEYS = ("guarded_calls", "clean_calls", "backoffs", "fallbacks",
               "breaker_trips", "parity_checks", "parity_mismatches",
               "numerics_faults", "injected_faults")

_lock = threading.RLock()
_strict: bool = False
_parity_mode: str = "off"
_parity_every: int = DEFAULT_PARITY_EVERY
_injector: Any = _NULL_INJECTOR
_counts: Dict[str, int] = {}
_breakers: Dict[Tuple[str, str], "_Breaker"] = {}
_parity_counter: Dict[Tuple[str, str], int] = {}
_last_ladder: Dict[Tuple[str, str], List[int]] = {}


@dataclasses.dataclass
class _Breaker:
    state: str = "closed"          # closed | open | half_open
    failures: int = 0              # consecutive, while closed
    open_calls: int = 0            # guarded calls seen while open


def _key(kernel: str) -> Tuple[str, str]:
    return (_machine_name(), kernel)


def _env_parity() -> Tuple[str, int]:
    mode = os.environ.get(PARITY_ENV, "off").strip().lower()
    if mode not in ("off", "sampled", "full"):
        mode = "off"
    try:
        every = max(1, int(os.environ.get(PARITY_EVERY_ENV,
                                          DEFAULT_PARITY_EVERY)))
    except ValueError:
        every = DEFAULT_PARITY_EVERY
    return mode, every


def reset() -> None:
    """Re-resolve from the environment with empty state (test isolation:
    the autouse conftest fixture calls this between tests)."""
    global _strict, _parity_mode, _parity_every, _injector
    with _lock:
        _strict = False
        _parity_mode, _parity_every = _env_parity()
        _injector = _NULL_INJECTOR
        _counts.clear()
        _counts.update({k: 0 for k in _COUNT_KEYS})
        _breakers.clear()
        _parity_counter.clear()
        _last_ladder.clear()


reset()


def set_strict(on: bool) -> None:
    """Disable degradation: failures raise their typed `SubstrateError`
    instead of walking the ladder / falling back (``--strict`` CI lanes)."""
    global _strict
    _strict = bool(on)


def strict_mode() -> bool:
    return _strict


def set_parity(mode: str, every: Optional[int] = None) -> None:
    """Set the sentinel mode: ``off`` | ``sampled`` (1-in-`every`) |
    ``full`` (every concrete call)."""
    global _parity_mode, _parity_every
    if mode not in ("off", "sampled", "full"):
        raise ValueError(f"parity mode must be off|sampled|full, got {mode!r}")
    _parity_mode = mode
    if every is not None:
        _parity_every = max(1, int(every))


def parity_mode() -> str:
    return _parity_mode


def set_injector(injector: Optional[Any]) -> None:
    """Install a `serve.faults.FaultInjector` (or None to clear) whose
    kernel-site streams fire inside every guarded call."""
    global _injector
    _injector = injector if injector is not None else _NULL_INJECTOR


def breaker_state(kernel: str) -> str:
    with _lock:
        br = _breakers.get(_key(kernel))
        return br.state if br is not None else "closed"


def last_ladder(kernel: str) -> List[int]:
    """Depths attempted by the most recent guarded call for `kernel` under
    the active machine (monotonically halving on failure)."""
    with _lock:
        return list(_last_ladder.get(_key(kernel), ()))


def stats() -> Dict[str, Any]:
    """Plain-int substrate totals (process-wide; survives
    ``REPRO_TELEMETRY=0``). `telemetry_summary()` and the default metrics
    registry fold this in as the ``substrate`` section/view."""
    with _lock:
        out: Dict[str, Any] = {k: _counts.get(k, 0) for k in _COUNT_KEYS}
        out["strict"] = _strict
        out["parity"] = _parity_mode
        out["breakers"] = {k[1]: br.state for k, br in sorted(_breakers.items())
                           if br.state != "closed"}
    return out


def _count(name: str, n: int = 1) -> None:
    with _lock:
        _counts[name] = _counts.get(name, 0) + n
    _registry_counter(f"substrate.{name}").inc(n)


def _registry_counter(name: str):
    from repro.obs import metrics
    return metrics.default_registry().counter(name)


def _tracer():
    from repro.obs import trace
    return trace.get_tracer()


# ------------------------------------------------------------- the breaker


_BREAKER_GAUGE = {"closed": 0.0, "half_open": 0.5, "open": 1.0}


def _transition(kernel: str, br: _Breaker, state: str) -> None:
    if br.state == state:
        return
    br.state = state
    if state == "open":
        br.open_calls = 0
        _count("breaker_trips")
    from repro.obs import metrics
    metrics.default_registry().gauge(
        f"substrate.breaker.{kernel}").set(_BREAKER_GAUGE[state])
    from repro.obs import trace
    _tracer().instant(f"breaker_{state}", tid=trace.TID_KERNEL, kernel=kernel)


def _note_failure(kernel: str, br: _Breaker) -> None:
    br.failures += 1
    if br.state == "half_open":
        _transition(kernel, br, "open")       # probe failed: re-open
    elif br.state == "closed" and br.failures >= BREAKER_THRESHOLD:
        _transition(kernel, br, "open")


def _note_success(kernel: str, br: _Breaker) -> None:
    br.failures = 0
    if br.state != "closed":
        _transition(kernel, br, "closed")


# -------------------------------------------------------- output policing


def _is_concrete(x: Any) -> bool:
    return not any(isinstance(leaf, jax.core.Tracer)
                   for leaf in jax.tree_util.tree_leaves(x))


def scan_output(kernel: str, out: Any, *,
                depth: Optional[int] = None) -> Optional[KernelNumericsError]:
    """The always-on NaN/Inf scan: returns a `KernelNumericsError` if any
    concrete floating leaf of `out` is non-finite, else None. Skipped under
    jit tracing (no concrete values to police)."""
    if not _is_concrete(out):
        return None
    for leaf in jax.tree_util.tree_leaves(out):
        if not hasattr(leaf, "dtype"):
            continue
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            continue
        if not bool(jnp.isfinite(leaf).all()):
            return numerics_fault(kernel, depth=depth)
    return None


def numerics_fault(kernel: str, *,
                   depth: Optional[int] = None) -> KernelNumericsError:
    """Count and trace one non-finite output of `kernel`; returns the typed
    error for the caller to raise (`scan_output`, and callers that check a
    finite flag their jitted step computed on the device)."""
    _count("numerics_faults")
    from repro.obs import trace
    _tracer().instant("substrate_nonfinite", tid=trace.TID_KERNEL,
                      kernel=kernel, depth=depth)
    return KernelNumericsError("non-finite values in kernel output",
                               kernel=kernel, depth=depth)


def _tolerance(leaves: Sequence[Any]) -> Tuple[float, float]:
    for leaf in leaves:
        if hasattr(leaf, "dtype") and leaf.dtype in (jnp.bfloat16, jnp.float16):
            return 3e-2, 3e-2
    return 2e-3, 2e-3


def parity_matches(out: Any, ref: Any) -> bool:
    """Leafwise allclose at the guard's tolerances (bf16/f16 anywhere in
    `out`: 3e-2, else 2e-3); integer leaves must match exactly."""
    a = jax.tree_util.tree_leaves(out)
    b = jax.tree_util.tree_leaves(ref)
    if len(a) != len(b):
        return False
    rtol, atol = _tolerance(a)
    for x, y in zip(a, b):
        x = jnp.asarray(x)
        y = jnp.asarray(y)
        if x.shape != y.shape:
            return False
        if jnp.issubdtype(x.dtype, jnp.floating) \
                or jnp.issubdtype(y.dtype, jnp.floating):
            ok = jnp.allclose(x.astype(jnp.float32), y.astype(jnp.float32),
                              rtol=rtol, atol=atol)
        else:
            ok = (x == y).all()
        if not bool(ok):
            return False
    return True


# --------------------------------------------------------- fault injection


def check_injected(kernel: str, injector: Optional[Any] = None,
                   **ctx: Any) -> None:
    """Fire the kernel-site fault streams and raise the matching typed
    error. Engine call sites use this *before* a donating jit call — pool
    buffers must not be consumed by an attempt that is about to fail."""
    inj = injector if injector is not None else _injector
    if inj.fire("kernel_compile", kernel=kernel, **ctx):
        _count("injected_faults")
        raise KernelCompileError("injected kernel compile failure",
                                 kernel=kernel, injected=True)
    if inj.fire("kernel_oom", kernel=kernel, **ctx):
        _count("injected_faults")
        raise KernelResourceError("injected RESOURCE_EXHAUSTED",
                                  kernel=kernel, injected=True)
    if inj.fire("kernel_nan", kernel=kernel, **ctx):
        _count("injected_faults")
        raise KernelNumericsError("injected non-finite kernel output",
                                  kernel=kernel, injected=True)


def _inject_pre(kernel: str, depth: int) -> None:
    if _injector.fire("kernel_compile", kernel=kernel, depth=depth):
        _count("injected_faults")
        raise KernelCompileError("injected kernel compile failure",
                                 kernel=kernel, depth=depth, injected=True)
    if _injector.fire("kernel_oom", kernel=kernel, depth=depth):
        _count("injected_faults")
        raise KernelResourceError("injected RESOURCE_EXHAUSTED",
                                  kernel=kernel, depth=depth, injected=True)


def _inject_poison(kernel: str, out: Any) -> Any:
    """kernel_nan stream: poison the first floating leaf of a successful
    attempt's output so the always-on scan must catch it."""
    if not _injector.fire("kernel_nan", kernel=kernel):
        return out
    _count("injected_faults")
    leaves, treedef = jax.tree_util.tree_flatten(out)
    for i, leaf in enumerate(leaves):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            leaves[i] = jnp.full_like(leaf, jnp.nan)
            return jax.tree_util.tree_unflatten(treedef, leaves)
    return out


# ------------------------------------------------------------ guarded_call


@dataclasses.dataclass
class GuardResult:
    """What a guarded call produced and how it got there."""

    out: Any
    depth: int
    path: str           # clean | backoff | twin | breaker
    t0: float = 0.0     # perf_counter at the start of the successful attempt

    @property
    def fallback(self) -> bool:
        return self.path in ("twin", "breaker")


def _resolve_twin(kernel: str) -> Optional[Callable[..., Any]]:
    try:
        from repro import kernels as kernels_pkg
        return kernels_pkg.fallback_twin(kernel)
    except Exception:  # pragma: no cover - registry import must not gate
        return None


def _classify(exc: Exception, kernel: str, depth: int,
              tile: Optional[Tuple[int, ...]]) -> SubstrateError:
    if isinstance(exc, SubstrateError):
        return exc
    msg = f"{type(exc).__name__}: {exc}"
    cls = KernelCompileError
    low = msg.lower()
    if any(marker in low for marker in _RESOURCE_MARKERS):
        cls = KernelResourceError
    err = cls(msg, kernel=kernel, depth=depth, tile=tile)
    err.__cause__ = exc
    return err


def _spec_tile(spec: Any) -> Optional[Tuple[int, ...]]:
    streams = (*getattr(spec, "loads", ()), *getattr(spec, "stores", ()))
    return tuple(streams[0].tile) if streams else None


def _run_twin(spec: Any, operands: Sequence[Any],
              twin: Callable[..., Any], depth: int, path: str,
              cause: Optional[SubstrateError]) -> GuardResult:
    _count("fallbacks")
    from repro.obs import trace
    _tracer().instant("substrate_fallback", tid=trace.TID_KERNEL,
                      kernel=spec.name, path=path,
                      error=type(cause).__name__ if cause else None)
    t0 = time.perf_counter()
    try:
        out = twin(spec, *operands)
    except Exception as twin_exc:
        if cause is not None:
            raise cause from twin_exc
        raise
    return GuardResult(out=out, depth=depth, path=path, t0=t0)


def _maybe_parity(spec: Any, operands: Sequence[Any], out: Any, depth: int,
                  twin: Optional[Callable[..., Any]]) -> Tuple[Any, bool]:
    """Returns (output, mismatched). On mismatch the twin's output is
    substituted (non-strict) or `KernelParityError` raised (strict)."""
    if twin is None or _parity_mode == "off":
        return out, False
    if not _is_concrete(out) or not _is_concrete(operands):
        return out, False
    key = _key(spec.name)
    with _lock:
        n = _parity_counter.get(key, 0) + 1
        _parity_counter[key] = n
    if _parity_mode == "sampled" and (n - 1) % _parity_every:
        return out, False
    _count("parity_checks")
    try:
        ref = twin(spec, *operands)
    except Exception:
        return out, False           # the twin cannot police this call
    if parity_matches(out, ref):
        return out, False
    _count("parity_mismatches")
    from repro.obs import trace
    _tracer().instant("parity_mismatch", tid=trace.TID_KERNEL,
                      kernel=spec.name, depth=depth)
    if _strict:
        raise KernelParityError("kernel output diverged from jnp twin",
                                kernel=spec.name, depth=depth,
                                tile=_spec_tile(spec))
    return ref, True


def guarded_call(spec: Any, operands: Sequence[Any],
                 attempt: Callable[[int], Any], *,
                 depth: int, n_tiles: int) -> GuardResult:
    """Run `attempt(depth)` under the substrate guard.

    `attempt` must rebuild the kernel for the depth it is given (scratch
    shapes re-derived each step — `coro_call` closes over its pallas_call
    builder). On failure the depth ladder halves toward 1; on exhaustion
    the registered jnp twin answers; parity/NaN policing and the breaker
    wrap every path. Raises only in strict mode, on KeyboardInterrupt /
    SystemExit, or when no twin is registered for `spec.name`.
    """
    kernel = spec.name
    key = _key(kernel)
    tile = _spec_tile(spec)
    twin = _resolve_twin(kernel)
    _count("guarded_calls")
    with _lock:
        br = _breakers.setdefault(key, _Breaker())

    # breaker routing (never in strict mode: strict means "surface it")
    if not _strict and br.state == "open":
        br.open_calls += 1
        if br.open_calls < BREAKER_COOLDOWN_CALLS:
            if twin is not None:
                return _run_twin(spec, operands, twin, depth, "breaker", None)
            # no twin to route to: attempt anyway
        else:
            _transition(kernel, br, "half_open")   # cooldown over: probe

    from repro.obs import trace
    tracer = _tracer()
    ladder: List[int] = []
    d = min(int(depth), n_tiles) if n_tiles > 0 else int(depth)
    err: Optional[SubstrateError] = None
    while True:
        ladder.append(d)
        t0 = time.perf_counter()
        try:
            _inject_pre(kernel, d)
            out = attempt(d)
            out = _inject_poison(kernel, out)
            nerr = scan_output(kernel, out, depth=d)
            if nerr is not None:
                raise nerr
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as exc:  # noqa: BLE001 - classified below
            err = _classify(exc, kernel, d, tile)
        else:
            err = None

        if err is None:
            out, mismatched = _maybe_parity(spec, operands, out, d, twin)
            with _lock:
                _last_ladder[key] = ladder
            if mismatched:
                # the twin's output was substituted: a correctness failure
                # feeds the breaker/quarantine exactly like a crash would
                _quarantine(kernel, d)
                _note_failure(kernel, br)
                _count("fallbacks")
                return GuardResult(out=out, depth=d, path="twin", t0=t0)
            _note_success(kernel, br)
            if len(ladder) == 1:
                _count("clean_calls")
                return GuardResult(out=out, depth=d, path="clean", t0=t0)
            return GuardResult(out=out, depth=d, path="backoff", t0=t0)

        # attempt at depth d failed
        _quarantine(kernel, d)
        _note_failure(kernel, br)
        if _strict:
            with _lock:
                _last_ladder[key] = ladder
            raise err
        if d <= 1:
            break
        nxt = max(1, d // 2)
        _count("backoffs")
        tracer.instant("substrate_backoff", tid=trace.TID_KERNEL,
                       kernel=kernel, from_depth=d, to_depth=nxt,
                       error=type(err).__name__)
        d = nxt

    with _lock:
        _last_ladder[key] = ladder
    if twin is None:
        raise err
    return _run_twin(spec, operands, twin, 1, "twin", err)


def _quarantine(kernel: str, depth: int) -> None:
    from repro.core import autotune
    autotune.quarantine_config(kernel, depth)
