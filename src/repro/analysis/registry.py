"""Kernel contract registry: every Pallas wrapper declares its grid contract.

A *kernel contract* is the set of facts about a ``pallas_call`` that the
type system cannot see but correctness depends on:

* every ``index_map`` stays inside the (padded) operand bounds over the
  whole grid — Pallas clamps out-of-bounds block indices silently, so a
  wrong map degrades results instead of crashing;
* blocks declared *lockstep* (e.g. the SlimSell-W weight block riding the
  cols block's scalar-prefetch indirection, or the pull kernel's not-final
  bitmap riding the output block) evaluate to identical block indices at
  every grid point — if they drift apart, weights pair with the wrong
  columns;
* output blocks are revisited **grid-contiguously** — the SlimChunk
  accumulation protocol re-initializes an output block on
  ``first_visit = (t == 0) | (blk != prev_blk)``, which is only sound if
  all visits to one block form a single contiguous run in grid order.

Kernel modules register their contract with ``@kernel_contract(cases)``
on the ``pallas_call`` wrapper; ``cases()`` builds the *real* grid-spec
objects (via the same builder the wrapper uses — nothing is re-declared,
so the contract cannot drift from the code) over a small demo layout.
``repro.analysis.contracts`` evaluates every case over the full grid.
This module holds only the registry + demo layout so kernel modules can
import it without pulling in the checker (and the checker imports the
kernels, not vice versa).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

#: registry of kernel-contract declarations, keyed by wrapper name
REGISTRY: Dict[str, "Registration"] = {}

#: selector into a case's specs: ("in", i) or ("out", i)
Selector = Tuple[str, int]


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One concrete instantiation of a kernel's grid contract.

    grid_spec:    the real ``PrefetchScalarGridSpec`` the wrapper would
                  build (same builder function — no re-declaration)
    scalar_args:  the scalar-prefetch operand values (numpy), appended to
                  the grid indices when evaluating each ``index_map``
    in_shapes:    logical array shape per non-prefetch input operand,
                  aligned with ``grid_spec.in_specs`` (None = untiled /
                  ANY-memory-space operand, skipped by the bounds check)
    out_shapes:   logical shape per output operand
    lockstep:     pairs of selectors whose block indices must be equal at
                  every grid point
    chunked_out:  selectors of outputs using SlimChunk accumulation, whose
                  distinct block indices must each form one contiguous run
                  in grid order
    """
    name: str
    grid_spec: Any
    scalar_args: Tuple[np.ndarray, ...]
    in_shapes: Sequence[Optional[Tuple[int, ...]]]
    out_shapes: Sequence[Tuple[int, ...]]
    lockstep: Sequence[Tuple[Selector, Selector]] = ()
    chunked_out: Sequence[Selector] = ()


@dataclasses.dataclass(frozen=True)
class Registration:
    fn: Any
    cases: Callable[[], List[KernelCase]]


def kernel_contract(cases: Callable[[], List[KernelCase]]):
    """Decorator for ``pallas_call`` wrappers: registers the wrapper's
    contract cases. The lint pass fails any ``pallas_call`` wrapper in
    ``repro.kernels`` that does not carry this decorator."""
    def deco(fn):
        name = getattr(fn, "__name__", None) or repr(fn)
        REGISTRY[name] = Registration(fn=fn, cases=cases)
        try:
            fn.__kernel_contract__ = True
        except (AttributeError, TypeError):
            pass  # jit wrappers may reject attributes; the registry is enough
        return fn
    return deco


# ------------------------------------------------------------- demo layout
#
# A handcrafted SlimSell tiling exercising every structural feature the
# contracts care about: multi-tile chunks (SlimChunk revisits), chunks
# crossing output-block boundaries, and a ragged final block.


def compact_ids_np(mask: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Numpy twin of ``kernels.ops.compact_tile_ids`` (the analysis layer
    cannot import the kernels — they import it)."""
    mask = np.asarray(mask, bool)
    order = np.argsort(~mask, kind="stable").astype(np.int32)
    n_active = int(mask.sum())
    ids = order.copy()
    ids[n_active:] = order[max(n_active - 1, 0)]
    return ids, np.asarray([n_active], np.int32)


def demo_layout() -> Dict[str, Any]:
    """Shapes + scalar-prefetch operands for the contract cases.

    row_block maps 9 tiles onto 5 chunks (chunk_blk=2 -> 3 output blocks):
    chunks 0/2/4 span multiple tiles (SlimChunk), chunk 1 shares an output
    block with chunk 0, and block 2 is ragged (only chunk 4).
    """
    row_block = np.asarray([0, 0, 1, 2, 2, 2, 3, 4, 4], np.int32)
    T = row_block.shape[0]
    n_chunks = 5
    chunk_blk = 2
    n_blk = -(-n_chunks // chunk_blk)
    C, L = 2, 4
    n_pad = 10
    full_ids = np.arange(T, dtype=np.int32)
    scenarios = [
        ("full", full_ids, np.asarray([T], np.int32)),
    ]
    # SlimWork subset: tiles {2, 6} inactive; the compacted tail repeats
    # the last active id, which must keep the revisit order contiguous
    mask = np.ones(T, bool)
    mask[[2, 6]] = False
    ids, n_active = compact_ids_np(mask)
    scenarios.append(("slimwork", ids, n_active))
    return dict(T=T, C=C, L=L, chunk_blk=chunk_blk, n_chunks=n_chunks,
                n_blk=n_blk, n_pad=n_pad, row_block=row_block,
                x_rows=(8, 128),  # n_pad laid out as kernels.sweep.as_rows
                scenarios=scenarios)
