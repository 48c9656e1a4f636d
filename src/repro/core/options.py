"""Single source of truth for the engine's public string options.

Every public entry point (``bfs``, ``multi_source_bfs``, ``sssp``, ``cc``,
``run_graph500*``, the ``make_dist_*`` factories) funnels its ``mode`` /
``direction`` / ``backend`` / ``semiring`` / ``comm`` strings through
``check_choice`` so a bad value fails *at the boundary* with one consistent
message — instead of deep inside a jit trace or, worse, silently falling
into a default branch (the old ``comm`` dispatch treated any unknown string
as ``reduce_gather``).

This module is the canonical home of the option *vocabularies* themselves:
``MODES``, ``COMMS``, ``BACKENDS``, ``DIRECTIONS``, ``SEMIRINGS`` (names —
the semiring *objects* live in ``core.semiring``, which asserts its registry
against this tuple at import time so the two can never drift), and the
subsets consumed by individual algorithms (``BFS_SEMIRINGS``,
``CC_SEMIRINGS``). The ``string-option`` lint rule in
``repro.analysis.lint`` enforces that public entry points dispatch only on
values validated against these constants.

It also owns the Pallas ``interpret`` default (``default_interpret``):
interpret mode on every non-TPU backend so the kernels are validated in CI,
compiled on real TPUs, overridable through the ``REPRO_PALLAS_INTERPRET``
environment variable. Forcing interpret mode on a TPU raises.

Since the serving PR this module also owns the **one** engine-knob record,
``EngineConfig``: a frozen dataclass bundling (backend, direction, mode,
interpret, comm, sanitize), validated once at construction. Every
algorithm front door (``bfs`` / ``multi_source_bfs`` / ``sssp`` /
``multi_source_sssp`` / ``cc``) and ``serving.GraphSession`` accept
``config=EngineConfig(...)``; the old per-call kwargs keep working through
``resolve_config``, which emits a ``DeprecationWarning`` carrying the
one-line migration.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import warnings
from typing import Optional, Sequence

MODES = ("fused", "hostloop")
COMMS = ("allreduce", "reduce_gather")
BACKENDS = ("jnp", "pallas")
DEFAULT_BACKEND = "jnp"
DIRECTIONS = ("push", "pull", "auto")

# the serving layer's query vocabulary: every GraphSession.submit() call
# names one of these (multi-source requests are streams of them)
ALGORITHMS = ("bfs", "sssp", "cc", "pagerank", "betweenness", "khop")

# query lifecycle states reported by serving.QueryResult.status: "shed"
# marks a query dropped at submit by the bounded-queue backpressure policy
QUERY_STATUSES = ("ok", "timeout", "shed")

# registered semiring names; core.semiring builds the object registry and
# asserts it matches this tuple at import time (the law verifier's
# cross-check then guarantees the kernel-side tables agree behaviorally).
# "boolean_packed" is SlimSell-B's word domain: boolean over packed uint32
# words, reached through the packed=True flag rather than named directly.
SEMIRINGS = ("tropical", "real", "boolean", "selmax", "minplus",
             "boolean_packed")

# the BFS engines accept exactly the paper's four; minplus is the
# SSSP/weighted operator and is rejected by bfs()/multi_source_bfs()
BFS_SEMIRINGS = ("tropical", "real", "boolean", "selmax")

# connected components: sel-max label propagation or boolean BFS peeling
CC_SEMIRINGS = ("selmax", "boolean")

# Pallas interpret-mode override: "auto" (default) = interpret off-TPU,
# compiled on TPU; "1"/"0" force it either way (calibration runs)
INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"

_INTERPRET_STATE = threading.local()


@contextlib.contextmanager
def interpret_override(value: Optional[bool]):
    """Thread-local override of the Pallas interpret default.

    ``EngineConfig.interpret`` threads through here: the kernels resolve
    their ``interpret`` flag at trace time via ``default_interpret()``, so a
    config with an explicit bool wraps the engine call in this context.
    ``None`` is a no-op (keep the env/auto default). Carries the same caveat
    as ``REPRO_PALLAS_INTERPRET``: jit caches key on the *functions*, so
    flipping the override mid-process only affects not-yet-traced shapes.
    """
    prev = getattr(_INTERPRET_STATE, "value", None)
    _INTERPRET_STATE.value = value
    try:
        yield
    finally:
        _INTERPRET_STATE.value = prev


def default_interpret() -> bool:
    """The repo-wide Pallas ``interpret`` default.

    Resolution order: an active ``interpret_override`` context (how
    ``EngineConfig.interpret`` lands), then ``REPRO_PALLAS_INTERPRET=1|0``;
    unset/"auto" interprets everywhere except on a real TPU backend.
    Interpret mode forced on a TPU raises (``resolve_interpret``).
    """
    override = getattr(_INTERPRET_STATE, "value", None)
    if override is not None:
        return resolve_interpret(bool(override))
    v = os.environ.get(INTERPRET_ENV, "auto").strip().lower()
    if v in ("1", "true", "yes"):
        return resolve_interpret(True)
    if v in ("0", "false", "no"):
        return False
    if v not in ("", "auto"):
        raise ValueError(
            f"bad {INTERPRET_ENV}={v!r}; expected 1, 0 or auto")
    import jax
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Map None -> the env-overridable repo default; pass explicit bools.

    Interpret mode on a TPU backend is an error, not a quiet slow path: a
    run on the chip either compiles its kernels or fails.
    """
    if interpret is None:
        return default_interpret()
    if interpret:
        import jax
        if jax.default_backend() == "tpu":
            raise RuntimeError(
                f"Pallas interpret mode requested on a TPU backend (via "
                f"{INTERPRET_ENV}, EngineConfig(interpret=True) or "
                f"interpret=True); the kernels run compiled on a TPU")
    return bool(interpret)


def check_choice(name: str, value, allowed: Sequence[str], *,
                 hint: str = ""):
    """Validate that ``value`` is one of ``allowed``; raise ValueError if not.

    Returns the value so call sites can validate inline:
    ``mode = check_choice("mode", mode, MODES)``.
    """
    if value not in allowed:
        opts = ", ".join(repr(a) for a in allowed)
        msg = f"unknown {name} {value!r}; expected one of: {opts}"
        if hint:
            msg += f" ({hint})"
        raise ValueError(msg)
    return value


# ------------------------------------------------------------- EngineConfig


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """The engine knobs as one validated, hashable record.

    backend:   "jnp" (reference) or "pallas" (SlimSell TPU kernels)
    direction: "push" | "pull" | "auto" (BFS-family; push-only algorithms
               require the default)
    mode:      "fused" (one on-device lax.while_loop) or "hostloop"
               (host loop + SlimWork tile gathering)
    interpret: Pallas interpret mode — None keeps the env/auto repo default
    comm:      distributed combine: "allreduce" | "reduce_gather"
    sanitize:  run engine calls under the checkify sanitizer
               (``core.debug.checked()``)

    Frozen + validated in ``__post_init__`` so a config is checked once and
    can key compile caches (``signature()``); accepted by every algorithm
    front door and ``serving.GraphSession`` as ``config=``.
    """
    backend: str = DEFAULT_BACKEND
    direction: str = "push"
    mode: str = "fused"
    interpret: Optional[bool] = None
    comm: str = "allreduce"
    sanitize: bool = False

    def __post_init__(self):
        check_choice("backend", self.backend, BACKENDS)
        check_choice("direction", self.direction, DIRECTIONS)
        check_choice("mode", self.mode, MODES)
        check_choice("comm", self.comm, COMMS)
        if self.interpret is not None and not isinstance(self.interpret, bool):
            raise ValueError(
                f"interpret must be None or bool, got {self.interpret!r}")
        if not isinstance(self.sanitize, bool):
            raise ValueError(f"sanitize must be bool, got {self.sanitize!r}")

    def signature(self) -> tuple:
        """Hashable identity for compile-cache / bucket keys."""
        return (self.backend, self.direction, self.mode, self.interpret,
                self.comm, self.sanitize)

    @contextlib.contextmanager
    def applied(self):
        """Context manager applying the config's ambient knobs (interpret
        override + sanitizer) around an engine call; backend/direction/mode
        are passed explicitly by the front doors."""
        from . import debug
        with contextlib.ExitStack() as stack:
            if self.interpret is not None:
                stack.enter_context(interpret_override(self.interpret))
            if self.sanitize and not debug.enabled():
                stack.enter_context(debug.checked())
            yield


def resolve_config(fn_name: str, config: Optional[EngineConfig],
                   **legacy) -> EngineConfig:
    """Merge a front door's deprecated per-call engine kwargs into one
    ``EngineConfig``.

    ``legacy`` holds the per-call kwargs with ``None`` meaning "not given".
    Passing both ``config=`` and a legacy kwarg is an error (silently
    preferring either would mask a caller bug). Legacy use warns with the
    one-line migration; construction validates every field via
    ``check_choice`` so the old error messages are preserved.
    """
    given = {k: v for k, v in legacy.items() if v is not None}
    if config is not None:
        if given:
            raise TypeError(
                f"{fn_name}: pass either config= or the per-call "
                f"{sorted(given)} kwargs, not both")
        if not isinstance(config, EngineConfig):
            raise TypeError(f"{fn_name}: config must be an EngineConfig, "
                            f"got {type(config).__name__}")
        return config
    if given:
        args = ", ".join(f"{k}={v!r}" for k, v in sorted(given.items()))
        warnings.warn(
            f"{fn_name}: per-call engine kwargs are deprecated; use "
            f"config=EngineConfig({args})", DeprecationWarning, stacklevel=3)
    return EngineConfig(**given)
