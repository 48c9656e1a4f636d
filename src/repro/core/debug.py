"""Checkify sanitizer mode: run any engine strategy with runtime invariant
checks compiled into the trace.

The kernels' correctness rests on data invariants the type system cannot
see: every non-padding column index must stay inside the padded frontier
(``jnp.take`` silently *clips* out-of-bounds gathers, so a corrupt layout
degrades distances instead of crashing), and a sweep under a semiring whose
zero is finite must never produce NaN/inf (under tropical, +inf is the
additive identity and legitimate; under real/boolean/selmax it means
overflow or a poisoned operand). ``checked()`` threads
``jax.experimental.checkify`` through the engine so those conditions become
hard errors:

    from repro.core import debug
    with debug.checked():
        res = bfs(tiled, 0, backend="pallas", mode="fused")

Covered strategies: fused (the whole ``lax.while_loop`` is checkified, so
per-iteration sweep checks accumulate through the loop carry), hostloop
(each jitted step is checkified; the layout is additionally validated
eagerly on host), and distributed (the ``make_dist_*`` runners route
through a checkified twin of the shard-mapped fixpoint — the runners
pass ``check_vma=False``, which checkify requires). Sanitized Pallas runs
keep the user checks but not checkify's index checks (``_errors_for``).

Mechanics: entering ``checked()`` sets a thread-local error set; the engine
routes execution to a cached ``jax.jit(checkify.checkify(impl))`` twin of
the normal jitted function. Check predicates are *emitted at trace time*
only while such a twin is tracing (``_EMIT``), so the normal path's traces
never contain unfunctionalized ``check`` primitives and the sanitized
path's traces always do — the two live in separate jit caches keyed by
function identity. ``CI`` runs a sanitized tier-1 smoke subset by exporting
``REPRO_SANITIZE=1`` (picked up at import).
"""
from __future__ import annotations

import contextlib
import functools
import inspect
import os
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import checkify

SANITIZE_ENV = "REPRO_SANITIZE"

_STATE = threading.local()


class SanitizerError(AssertionError):
    """Raised by the eager host-side layout validation."""


def _get(attr, default=None):
    return getattr(_STATE, attr, default)


def enabled() -> bool:
    """True when the current thread is inside ``checked()`` (or the process
    was started with ``REPRO_SANITIZE=1``)."""
    return _get("errors") is not None


def errors() -> Optional[frozenset]:
    """The active checkify error set, or None when the sanitizer is off."""
    return _get("errors")


def _emitting() -> bool:
    return bool(_get("emit", False))


def default_errors(*, index_checks: bool = True,
                   nan_checks: bool = False) -> frozenset:
    """user_checks always (the explicit invariants below); ``index_checks``
    adds checkify's OOB instrumentation on indexing primitives;
    ``nan_checks`` adds the global float instrumentation — off by default
    because tropical/min-plus legitimately traffic in +inf (the targeted
    ``check_sweep`` covers NaN/inf per semiring instead)."""
    errs = checkify.user_checks
    if index_checks:
        errs = errs | checkify.index_checks
    if nan_checks:
        errs = errs | checkify.float_checks
    return errs


@contextlib.contextmanager
def checked(errors: Optional[frozenset] = None, *,
            index_checks: bool = True, nan_checks: bool = False):
    """Context manager: run the enclosed engine calls sanitized.

    ``errors`` overrides the checkify error set entirely; otherwise it is
    built by ``default_errors(index_checks=, nan_checks=)``.
    """
    errs = default_errors(index_checks=index_checks, nan_checks=nan_checks) \
        if errors is None else frozenset(errors)
    prev = _get("errors")
    _STATE.errors = errs
    try:
        yield
    finally:
        _STATE.errors = prev


@contextlib.contextmanager
def suspended():
    """Context manager: run the enclosed calls with the sanitizer OFF,
    restoring the previous state on exit — the inverse of ``checked()``,
    for skipping a known-noisy region of a ``REPRO_SANITIZE=1`` run."""
    prev = _get("errors")
    _STATE.errors = None
    try:
        yield
    finally:
        _STATE.errors = prev


def enable(**kw) -> None:
    """Turn the sanitizer on for the current thread until ``disable()``."""
    _STATE.errors = default_errors(**kw)


def disable() -> None:
    _STATE.errors = None


# ----------------------------------------------------- trace-time predicates
#
# These helpers are called unconditionally from the engine's hot paths and
# compile to NOTHING unless a checkified twin is currently tracing — the
# emit flag is only set around `call_checked`, so normal traces never carry
# check primitives (which would fail to lower outside checkify).


def check(pred, msg: str, **fmt) -> None:
    """Emit ``checkify.check`` when tracing under the sanitizer; no-op
    otherwise."""
    if _emitting():
        checkify.check(pred, msg, **fmt)


def check_layout(tiled) -> None:
    """Structural layout invariants, checked once per run: every column
    slot is -1 (padding) or a valid vertex id < n, and stored weights are
    finite and non-negative on non-padding slots."""
    if not _emitting():
        return
    cols = tiled.cols
    checkify.check(jnp.all(cols >= -1),
                   "SlimSell cols contains ids < -1 (corrupt layout)")
    checkify.check(jnp.all(cols < tiled.n),
                   "SlimSell cols contains out-of-bounds vertex ids "
                   "(>= n): gather would silently clip")
    wts = getattr(tiled, "wts", None)
    if wts is not None:
        live = tiled.cols >= 0
        ok = jnp.where(live, jnp.isfinite(wts) & (wts >= 0), True)
        checkify.check(jnp.all(ok),
                       "SlimSell-W wts has NaN/inf/negative weights on "
                       "non-padding slots")


def check_gather(idx, n: int) -> None:
    """Gather-operand bound check (the frontier gather clips OOB silently)."""
    if _emitting():
        checkify.check(jnp.all((idx >= 0) & (idx < n)),
                       f"gather index out of bounds [0, {n})")


def check_sweep(sr, y, n_bits: Optional[int] = None) -> None:
    """Post-sweep value sanity, per semiring: float sweeps must never
    produce NaN; semirings whose zero is finite must not overflow to the
    *poison* infinity. The reduction kind's own fill identity is allowed:
    segment_max fills empty output segments (rows with no live columns in
    a SlimWork subset sweep) with -inf, which the update treats as "no
    contribution" — so a max-kind sweep only flags +inf, a min-kind only
    -inf, and a sum-kind flags both. Under tropical/min-plus (infinite
    zero) inf is the additive identity and no finiteness check applies.

    Packed (SlimSell-B) sweeps pass ``n_bits`` — the live-bit count of the
    packed word axis (the LAST axis) — and get the tail-word invariant
    instead: every padding bit above ``n_bits`` must be zero. A set padding
    bit would survive every OR downstream and resurface as a phantom
    vertex/root after unpack."""
    if not _emitting():
        return
    if n_bits is not None and jnp.issubdtype(y.dtype, jnp.unsignedinteger):
        from . import packing
        mask = jnp.asarray(packing._cached_padding_mask(int(n_bits)))
        checkify.check(~jnp.any(y & ~mask),
                       f"packed {sr.name} sweep has nonzero tail padding "
                       f"bits (live bits: {int(n_bits)}) — the tail-word "
                       "invariant is broken")
        return
    if not jnp.issubdtype(y.dtype, jnp.floating):
        return
    checkify.check(~jnp.any(jnp.isnan(y)),
                   f"NaN in {sr.name}-semiring sweep output")
    if np.isfinite(sr.zero):
        if sr.reduction == "max":
            bad = jnp.isposinf(y)
        elif sr.reduction == "min":
            bad = jnp.isneginf(y)
        else:
            bad = ~jnp.isfinite(y)
        checkify.check(~jnp.any(bad),
                       f"poison infinity in {sr.name}-semiring sweep "
                       f"(zero is finite, reduction is {sr.reduction}: "
                       "this means overflow or a corrupted operand)")


# ------------------------------------------------------- checkified calling


_CACHE: dict = {}


def checkified(fn, *, static_argnames=(), errs: Optional[frozenset] = None):
    """A cached ``jax.jit(checkify.checkify(fn, errs))`` twin of ``fn``."""
    errs = errs if errs is not None else (errors() or default_errors())
    key = (fn, errs, tuple(static_argnames))
    cf = _CACHE.get(key)
    if cf is None:
        cf = jax.jit(checkify.checkify(fn, errors=errs),
                     static_argnames=tuple(static_argnames))
        _CACHE[key] = cf
    return cf


def _errors_for(pallas: bool) -> frozenset:
    """The active error set, less checkify's index checks when the traced
    function runs Pallas kernels: JAX 0.9's Pallas checkify rule traces its
    grid-bounds check with float32 zero blocks and fails on every int32
    block operand (each SlimSell kernel's ``cols``). The user checks stay —
    ``check_layout`` bounds every column id the kernels read."""
    errs = errors() or default_errors()
    return errs - checkify.index_checks if pallas else errs


def call_checked(fn, *args, static_argnames=(), pallas: bool = False,
                 **kwargs):
    """Run ``fn`` through its checkified twin, emitting the engine's
    invariant checks during the trace, and throw on any error. ``pallas``
    marks a function that runs Pallas kernels (see ``_errors_for``)."""
    cf = checkified(fn, static_argnames=static_argnames,
                    errs=_errors_for(pallas))
    # the checkify wrapper erases fn's signature, so positional statics
    # would not match static_argnames — bind everything to keywords
    bound = inspect.signature(fn).bind(*args, **kwargs)
    prev = _get("emit", False)
    _STATE.emit = True
    try:
        err, out = cf(**bound.arguments)
    finally:
        _STATE.emit = prev
    err.throw()
    return out


def jit_checked(fn, *, pallas: bool = False):
    """Drop-in replacement for ``jax.jit(fn)`` (no static args) that routes
    each call through a checkified twin while the sanitizer is active — the
    distributed factories return this so ``make_dist_*`` runners pick up
    ``checked()`` at call time, not factory time. ``pallas`` as in
    ``call_checked``."""
    jitted = jax.jit(fn)

    @functools.wraps(fn)
    def call(*args, **kwargs):
        if not enabled():
            return jitted(*args, **kwargs)
        cf = checkified(fn, errs=_errors_for(pallas))
        prev = _get("emit", False)
        _STATE.emit = True
        try:
            err, out = cf(*args, **kwargs)
        finally:
            _STATE.emit = prev
        err.throw()
        return out

    return call


# -------------------------------------------------- eager host-side checks


def validate_layout_host(tiled) -> None:
    """Eager numpy twin of ``check_layout`` for the hostloop strategy (and
    anyone wanting a pre-flight check without tracing)."""
    cols = np.asarray(tiled.cols)
    if cols.min(initial=0) < -1:
        raise SanitizerError("SlimSell cols contains ids < -1")
    if cols.max(initial=-1) >= tiled.n:
        raise SanitizerError(
            f"SlimSell cols contains out-of-bounds vertex ids "
            f"(max {int(cols.max())} >= n={tiled.n})")
    wts = getattr(tiled, "wts", None)
    if wts is not None:
        w = np.asarray(wts)
        live = cols >= 0
        bad = live & (~np.isfinite(w) | (w < 0))
        if bad.any():
            raise SanitizerError(
                "SlimSell-W wts has NaN/inf/negative weights on "
                f"{int(bad.sum())} non-padding slots")


if os.environ.get(SANITIZE_ENV, "").strip().lower() in ("1", "true", "yes"):
    enable()
