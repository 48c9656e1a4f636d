"""Distributed 2D algebraic traversal (DESIGN.md §3; Buluç–Madduri [9] layout).

The adjacency is partitioned 2D: chunk rows over the mesh row axes
(``pod`` × ``data``) and vertex columns over the mesh column axis (``model``).
Each device owns the SlimSell tiles of its (row-range, column-range) block,
with column indices *localized* to its column range.

Since PR 4 the distributed loop is the third strategy of the shared fixpoint
engine (``core.engine``): **any** ``FixpointSpec`` — single-source BFS,
batched multi-source BFS, flattened delta-stepping SSSP (single-source and
batched over the column-sharded distance matrix), CC label propagation —
runs over the 2D partition with no per-algorithm distributed code. One
iteration on device (i, j):

  1. local sweep over the owned tiles via the ordinary ``slimsell_spmv`` /
     ``slimsell_pull`` / ``slimsell_spmm`` primitives (the local layout is a
     duck-typed tiled view whose *global* ``row_vertex`` ids scatter straight
     into full vertex space; no communication),
  2. semiring all-reduce of y over (col_axes + row_axes)  [baseline], or
     semiring reduce along ``model`` + row-axis combine [``reduce_gather``],
  3. the spec's own replicated state update — identical math to the
     single-device engine.

``direction="pull"`` masks the local sweep to the shard's not-final rows
(SlimWork's tile criterion on the local ``row_vertex``) — the "local row
sweep + row-axis gather" decomposition; ``"auto"`` runs the replicated
Beamer heuristic and a ``lax.cond`` picks per iteration.

``partition_slimsell`` builds real data for tests (carrying per-slot
weights and the degree vector when the CSR has them); the dry-run lowers the
same factories with ShapeDtypeStructs only. ``make_dist_bfs_sliced`` is the
separately-tuned slot-space BFS hillclimb (frontier slices + grid-transpose
exchange) and bypasses the generic engine.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from . import debug
from . import engine as eng
from .betweenness import BRANDES_BACKWARD_SPEC, BRANDES_FORWARD_SPEC
from .bfs import bfs_spec
from .cc import CC_SPEC
from .engine import DIRECTIONS, WORK_LOG, FixpointSpec
from .formats import CSRGraph, build_push_index, sellcs_order
from .multi_bfs import multi_bfs_spec, packed_multi_bfs_spec
from .multi_sssp import MULTI_SSSP_SPEC
from .options import COMMS, check_choice
from .pagerank import PAGERANK_MAX_ITERS, PAGERANK_SPEC, pagerank_views
from .spmv import resolve_backend
from .sssp import SSSP_SPEC

Array = jax.Array


@dataclasses.dataclass
class DistSlimSell:
    """2D-partitioned SlimSell. Leading [R, Co] axes are the device grid.

    ``wts`` (the SlimSell-W weight slots, aligned with ``cols``) and ``deg``
    (the replicated degree vector the direction heuristic reads) exist only
    when the source CSR carries them / they are needed; both default None so
    ShapeDtypeStruct-only metas keep lowering.
    """
    n: int
    C: int
    L: int
    R: int                  # row shards (pod*data)
    Co: int                 # column shards (model)
    n_col: int              # vertices per column range (padded)
    chunks_per_shard: int
    t_max: int
    cols: np.ndarray        # int32[R, Co, T, C, L] localized (-1 pad)
    row_block: np.ndarray   # int32[R, Co, T] chunk index *within shard*
    row_vertex: np.ndarray  # int32[R, chunks_per_shard, C] global vertex ids
    wts: Optional[np.ndarray] = None  # float32[R, Co, T, C, L] slot weights
    deg: Optional[np.ndarray] = None  # int64[n] degree vector (replicated)
    # per-shard push index (SlimWork push masks on the mesh): deduplicated
    # (localized column, tile) pairs of each shard's block, padded to the
    # widest shard's pair count with (0, t_max) — the OOB tile id makes
    # segment ops drop the padding
    inc_src: Optional[np.ndarray] = None  # int32[R, Co, K] localized col ids
    inc_tile: Optional[np.ndarray] = None  # int32[R, Co, K] tile ids


def _tiled_flatten(t):
    return (t.cols, t.row_block, t.row_vertex, t.wts, t.deg,
            t.inc_src, t.inc_tile), (
        t.n, t.C, t.L, t.R, t.Co, t.n_col, t.chunks_per_shard, t.t_max)


def _tiled_unflatten(aux, ch):
    n, C, L, R, Co, n_col, cps, t_max = aux
    return DistSlimSell(n=n, C=C, L=L, R=R, Co=Co, n_col=n_col,
                        chunks_per_shard=cps, t_max=t_max,
                        cols=ch[0], row_block=ch[1], row_vertex=ch[2],
                        wts=ch[3], deg=ch[4], inc_src=ch[5], inc_tile=ch[6])


jax.tree_util.register_pytree_node(DistSlimSell, _tiled_flatten, _tiled_unflatten)


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """A device mesh for the ``make_dist_*`` factories: ``jax.make_mesh``
    with Auto axis types (the shard-mapped sweeps place their operands with
    explicit ``PartitionSpec``s, not sharding-in-types)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def partition_slimsell(csr: CSRGraph, R: int, Co: int, *, C: int = 8,
                       L: int = 128, sigma: Optional[int] = None,
                       slot_space: bool = False) -> DistSlimSell:
    """Host-side 2D partition of the SlimSell layout.

    If the CSR carries weights, the partition also carries the per-slot
    ``wts`` blocks (localized in lockstep with ``cols``) so the weighted
    min-plus operators (distributed SSSP) run over it. ``deg`` always rides
    along for the direction heuristic, and every partition carries the
    per-shard push index (``inc_src`` / ``inc_tile``) so the engine's
    SlimWork push masks work on the mesh (``make_dist_* (slimwork=True)``).

    slot_space=True renumbers vertices by their sorted-row slot (the
    optimized layout, EXPERIMENTS.md §Perf): row shard i then owns the
    *contiguous* slot range [i·cps·C, (i+1)·cps·C), which turns the frontier
    exchange from a full-length all-reduce into a row-sliced reduce plus an
    n/Co fragment gather. ``row_vertex`` still maps slots back to original
    ids for the final un-permutation.
    """
    n, deg = csr.n, csr.deg
    weighted = csr.weights is not None
    sigma = n if sigma is None else max(1, min(int(sigma), n))
    perm = sellcs_order(deg, sigma)
    inv_perm = np.empty(n, np.int64)
    inv_perm[perm] = np.arange(n)
    n_chunks = math.ceil(n / C)
    cps = math.ceil(n_chunks / R)           # chunks per row shard
    n_pad = (cps * C * R) if slot_space else n
    n_col = math.ceil(n_pad / Co)

    row_vertex = np.full((R, cps, C), -1, np.int32)
    per_shard_tiles: list[list[list[tuple]]] = [
        [[] for _ in range(Co)] for _ in range(R)]

    for c in range(n_chunks):
        i = c // cps
        c_local = c % cps
        rows, wrows = [], []
        for r in range(C):
            row = c * C + r
            v = int(perm[row]) if row < n else -1
            row_vertex[i, c_local, r] = v
            s, e = (csr.indptr[v], csr.indptr[v + 1]) if v >= 0 else (0, 0)
            nbr = csr.indices[s:e] if v >= 0 else np.empty(0, np.int32)
            wrows.append(csr.weights[s:e] if weighted else None)
            if slot_space and nbr.size:
                nbr = inv_perm[nbr].astype(np.int32)
            rows.append(nbr)
        for j in range(Co):
            lo, hi = j * n_col, (j + 1) * n_col
            masks = [(r >= lo) & (r < hi) for r in rows]
            parts = [r[m] - lo for r, m in zip(rows, masks)]
            length = max((p.size for p in parts), default=0)
            if length == 0:
                continue
            width = math.ceil(length / L) * L
            buf = np.full((C, width), -1, np.int32)
            buf_w = np.zeros((C, width), np.float32) if weighted else None
            for r, p in enumerate(parts):
                buf[r, :p.size] = p
                if weighted:
                    buf_w[r, :p.size] = wrows[r][masks[r]]
            for t0 in range(0, width, L):
                per_shard_tiles[i][j].append(
                    (c_local, buf[:, t0:t0 + L],
                     buf_w[:, t0:t0 + L] if weighted else None))

    t_max = max(1, max(len(per_shard_tiles[i][j]) for i in range(R) for j in range(Co)))
    cols = np.full((R, Co, t_max, C, L), -1, np.int32)
    wts = np.zeros((R, Co, t_max, C, L), np.float32) if weighted else None
    row_block = np.zeros((R, Co, t_max), np.int32)
    for i in range(R):
        for j in range(Co):
            for t, (cl, buf, bw) in enumerate(per_shard_tiles[i][j]):
                cols[i, j, t] = buf
                row_block[i, j, t] = cl
                if weighted:
                    wts[i, j, t] = bw
            # padding tiles (all cols == -1) keep the last real chunk id so
            # grid order stays non-decreasing: the pallas kernel re-inits an
            # output block on every chunk-block change, and a tail that
            # jumped back to chunk 0 would wipe its accumulated values
            n_real = len(per_shard_tiles[i][j])
            if n_real and n_real < t_max:
                row_block[i, j, n_real:] = per_shard_tiles[i][j][-1][0]
    # per-shard push index: SlimWork push masks need (localized column,
    # tile) incidence per block; shards are padded to one common K so the
    # arrays shard cleanly, padding pairs pointing at the dropped tile id
    # t_max (out of segment range)
    pairs = [[build_push_index(cols[i, j]) for j in range(Co)]
             for i in range(R)]
    K = max(1, max(p[0].size for row in pairs for p in row))
    inc_src = np.zeros((R, Co, K), np.int32)
    inc_tile = np.full((R, Co, K), t_max, np.int32)
    for i in range(R):
        for j in range(Co):
            s, t = pairs[i][j]
            inc_src[i, j, :s.size] = s
            inc_tile[i, j, :t.size] = t
    return DistSlimSell(n=n, C=C, L=L, R=R, Co=Co, n_col=n_col,
                        chunks_per_shard=cps, t_max=t_max, cols=cols,
                        row_block=row_block, row_vertex=row_vertex,
                        wts=wts, deg=deg, inc_src=inc_src, inc_tile=inc_tile)


# ------------------------------------------------ optimized sliced exchange


def make_dist_bfs_sliced(mesh: Mesh, meta: DistSlimSell, *,
                         row_axis: str = "data", col_axis: str = "model",
                         pod_axis: Optional[str] = None, max_iters: int = 64,
                         frontier_dtype=jnp.float32):
    """Optimized tropical BFS over the *slot-space* partition
    (EXPERIMENTS.md §Perf, BFS hillclimb).

    Decomposition: vertex rows over ``data`` (R=16), vertex columns over
    ``model`` (Co=16, R == Co), and — on the multi-pod mesh — the *edges* of
    each (row, column) block over ``pod`` (3D SpMV: A = ⊕_pod A_p).

    Per iteration and device, instead of a full-length replicated-state
    all-reduce (ring bytes 2·n·b), communicate only:
      1. pmin over (pod, model) of the OWN row-range slice     2·(n/R)·b
      2. one collective-permute: the (data, model) grid transpose delivers
         f_j as the next frontier slice x_j                      (n/R)·b
    with b = frontier bytes (fp32 or bf16 — tropical distances are small
    ints, exactly representable in bf16). State stays sharded by row range;
    distances come back as [R, n/R] slot-space slices (``row_vertex``
    un-permutes them).
    """
    cps, C, L = meta.chunks_per_shard, meta.C, meta.L
    n_row = cps * C                       # slots per row shard
    R, Co = meta.R, meta.Co
    assert R == Co, "sliced mode uses a square (data x model) vertex grid"
    reduce_axes = (pod_axis, col_axis) if pod_axis else (col_axis,)
    all_axes = ((pod_axis,) if pod_axis else ()) + (row_axis, col_axis)
    transpose_perm = [(a * Co + b, b * R + a)
                      for a in range(R) for b in range(Co)]

    integer = jnp.issubdtype(jnp.dtype(frontier_dtype), jnp.integer)

    def bfs_shard(cols, row_block, root_slot):
        cols = cols.reshape(-1, C, L)
        row_block = row_block.reshape(-1)
        i = jax.lax.axis_index(row_axis)
        j = jax.lax.axis_index(col_axis)
        # integer frontier (int16): "infinity" is a sentinel; it drifts up by
        # 1 per iteration (min(INF)+1) and stays < int16 max for <2.7k iters
        inf = (jnp.asarray(30_000, frontier_dtype) if integer
               else jnp.asarray(jnp.inf, frontier_dtype))
        f_i = jnp.where(i * n_row + jnp.arange(n_row) == root_slot,
                        0, inf).astype(frontier_dtype)
        x_j = jnp.where(j * n_row + jnp.arange(n_row) == root_slot,
                        0, inf).astype(frontier_dtype)

        def body(carry):
            f_i, x_j, k, _ = carry
            pad = cols < 0
            safe = jnp.where(pad, 0, cols)
            g = jnp.take(x_j, safe, axis=0) + jnp.asarray(1, frontier_dtype)
            contrib = jnp.where(pad, inf, g)
            tile_red = contrib.min(axis=-1)                        # [T, C]
            y = jax.ops.segment_min(tile_red, row_block,
                                    num_segments=cps).reshape(n_row)
            # (1) combine partial mins for OWN rows across pod x model
            y = jax.lax.pmin(y, reduce_axes)
            f_new = jnp.minimum(f_i, y)
            changed = jnp.any(f_new < f_i)
            # (2) grid transpose: x_j for the next iteration is exactly f_j
            x_new = jax.lax.ppermute(f_new, (row_axis, col_axis),
                                     transpose_perm)
            changed = jax.lax.pmax(changed.astype(jnp.int32), all_axes) > 0
            return f_new, x_new, k + 1, changed

        def cond(carry):
            _, _, k, changed = carry
            return changed & (k <= max_iters)

        f_i, _, k, _ = jax.lax.while_loop(
            cond, body, (f_i, x_j, jnp.asarray(1, jnp.int32),
                         jnp.asarray(True)))
        unreached = (f_i >= inf) if integer else jnp.isinf(f_i)
        d_i = jnp.where(unreached, -1,
                        f_i.astype(jnp.float32).astype(jnp.int32))
        return d_i[None], k - 1

    lead = (pod_axis,) if pod_axis else ()
    cols_spec = P(*(lead + (row_axis, col_axis, None, None, None))) \
        if pod_axis else P(row_axis, col_axis, None, None, None)
    rb_spec = P(*(lead + (row_axis, col_axis, None))) \
        if pod_axis else P(row_axis, col_axis, None)
    sharded = jax.shard_map(
        lambda c, rb, r: bfs_shard(c, rb, r), mesh=mesh,
        in_specs=(cols_spec, rb_spec, P()),
        out_specs=(P(row_axis, None), P()),
        check_vma=False,
    )
    return debug.jit_checked(sharded)


# --------------------------------------------- generic engine-backed runner


def make_dist_fixpoint(mesh: Mesh, meta: DistSlimSell, spec: FixpointSpec, *,
                       row_axes: Sequence[str] = ("data",),
                       col_axes: Sequence[str] = ("model",),
                       max_iters: int = 64, comm: str = "allreduce",
                       backend: Optional[str] = None,
                       direction: str = "push", slimwork: bool = False,
                       finalize=None):
    """The distributed execution strategy: run any ``FixpointSpec`` over the
    2D partition. Returns a jitted function

        fn(cols, row_block, row_vertex[, inc_src, inc_tile][, deg][, wts],
           arg, ctx_args) -> finalize(state, iterations, dirs)

    ``deg`` is present only under ``direction="auto"`` (the heuristic input)
    and ``wts`` only for weighted specs; both extra operands keep the
    factory AOT-lowerable from ShapeDtypeStructs alone. ``slimwork=True``
    adds the per-shard push-index operands ``inc_src`` / ``inc_tile``
    (built by ``partition_slimsell``) so push sweeps mask to the tiles
    holding a frontier column — jnp backend on the mesh only, like the pull
    masks. ``ctx_args`` is the (possibly empty) tuple handed to the spec's
    ``setup`` — e.g. SSSP's traced delta. ``finalize`` maps the replicated
    final state to the outputs (default: the state dict itself plus the
    iteration count).
    """
    check_choice("direction", direction, DIRECTIONS)
    check_choice("direction", direction, spec.directions,
                 hint=f"supported by {spec.name}")
    check_choice("comm", comm, COMMS)
    backend = resolve_backend(backend)
    if slimwork and meta.inc_src is None:
        raise ValueError("slimwork=True needs the per-shard push index; "
                         "rebuild the partition with partition_slimsell")
    weighted = spec.weights is not None
    auto = direction == "auto"
    cps, C, L, t_max = meta.chunks_per_shard, meta.C, meta.L, meta.t_max
    if finalize is None:
        finalize = lambda state, iters, dirs: (state, iters)  # noqa: E731

    def shard_fn(cols, row_block, row_vertex, *rest):
        rest = list(rest)
        inc_src = rest.pop(0) if slimwork else None
        inc_tile = rest.pop(0) if slimwork else None
        deg = rest.pop(0) if auto else None
        wts = rest.pop(0) if weighted else None
        arg, ctx_args = rest
        local = eng._SubsetTiled(
            cols=cols.reshape(t_max, C, L),
            row_block=row_block.reshape(-1),
            row_vertex=row_vertex.reshape(cps, C),
            n=meta.n, n_chunks=cps,
            wts=None if wts is None else wts.reshape(t_max, C, L),
            inc_src=None if inc_src is None else inc_src.reshape(-1),
            inc_tile=None if inc_tile is None else inc_tile.reshape(-1))
        ctx = spec.setup(local, *ctx_args) if spec.setup is not None else None
        state = spec.init_state(meta.n, arg, ctx)
        d0 = jnp.asarray(eng.dm.PULL if direction == "pull" else eng.dm.PUSH,
                         jnp.int32)
        # the per-iteration direction log is only worth carrying (int32
        # [WORK_LOG] replicated per device) when the heuristic actually
        # runs AND a finalize wants it; push/pull runs reconstruct it from
        # the static direction for free
        dirs0 = jnp.full((WORK_LOG,), -1, jnp.int32) if auto \
            else jnp.zeros((1,), jnp.int32)

        def cond(carry):
            _, k, cont, _, _ = carry
            return cont & (k <= max_iters)

        def body(carry):
            state, k, _, dcur, dirs = carry
            dnext = eng.dist_choose_direction(spec, ctx, deg, state, k, dcur,
                                              meta.n) if auto else dcur
            state, cont = eng.dist_step(
                spec, ctx, local, state, k, dnext,
                n=meta.n, Co=meta.Co, n_col=meta.n_col,
                row_axes=row_axes, col_axes=col_axes, comm=comm,
                backend=backend, direction=direction)
            if auto:
                dirs = dirs.at[jnp.minimum(k - 1, WORK_LOG - 1)].set(dnext)
            return state, k + 1, cont, dnext, dirs

        state, k, _, _, dirs = jax.lax.while_loop(
            cond, body, (state, jnp.asarray(1, jnp.int32),
                         jnp.asarray(True), d0, dirs0))
        return finalize(state, k - 1, dirs)

    row = tuple(row_axes) if len(row_axes) > 1 else row_axes[0]
    block_spec = P(row, col_axes[0], None, None, None)
    in_specs = [block_spec, P(row, col_axes[0], None), P(row, None, None)]
    if slimwork:
        inc_spec = P(row, col_axes[0], None)  # inc_src / inc_tile
        in_specs.extend([inc_spec, inc_spec])
    if auto:
        in_specs.append(P())                  # deg, replicated
    if weighted:
        in_specs.append(block_spec)           # wts, in lockstep with cols
    in_specs.append(P())                      # arg
    in_specs.append(P())                      # ctx_args tuple (P() is a prefix)
    sharded = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=tuple(in_specs), out_specs=P(),
        check_vma=False,
    )
    return debug.jit_checked(sharded, pallas=backend == "pallas")


# ---------------------------------------------------- per-algorithm factories
#
# Each factory is only spec selection + a ``finalize`` projection — the
# ROADMAP's "distributed multi-source / pull-auto / SSSP / CC" items fall
# out of the engine with no per-algorithm distributed loop code.


def make_dist_bfs(mesh: Mesh, meta: DistSlimSell, sr_name: str = "tropical", *,
                  row_axes: Sequence[str] = ("data",),
                  col_axes: Sequence[str] = ("model",),
                  max_iters: int = 64, comm: str = "allreduce",
                  backend: Optional[str] = None, direction: str = "push",
                  slimwork: bool = False):
    """Jitted distributed BFS: (cols, row_block, row_vertex
    [, inc_src, inc_tile][, deg], root) -> (distances, iterations). ``meta``
    provides the static layout fields (arrays in it may be ShapeDtypeStructs
    for AOT lowering); the extra ``deg`` operand exists only under
    ``direction="auto"`` and the push-index operands only under
    ``slimwork=True``."""
    run = make_dist_fixpoint(
        mesh, meta, bfs_spec(sr_name), row_axes=row_axes, col_axes=col_axes,
        max_iters=max_iters, comm=comm, backend=backend, direction=direction,
        slimwork=slimwork,
        finalize=lambda state, iters, dirs: (state["d"], iters))
    return lambda *args: run(*args, ())


def make_dist_multi_bfs(mesh: Mesh, meta: DistSlimSell,
                        sr_name: str = "tropical", *,
                        row_axes: Sequence[str] = ("data",),
                        col_axes: Sequence[str] = ("model",),
                        max_iters: int = 64, comm: str = "allreduce",
                        backend: Optional[str] = None,
                        direction: str = "push", slimwork: bool = False,
                        packed: bool = False,
                        batch_width: Optional[int] = None):
    """Jitted distributed multi-source BFS over the column-sharded frontier
    matrix: (cols, row_block, row_vertex[, inc_src, inc_tile][, deg],
    roots[B]) -> (distances [B, n], iterations). One SpMM/pull-MM sweep per
    iteration advances every root; under ``direction="auto"`` the whole
    batch switches together (mean Beamer statistics — the SpMM advances
    every column on each active tile, so the union mask is the only one
    that matters). Under ``slimwork=True`` push sweeps mask to the tiles
    holding a frontier column via the partition's per-shard push index.

    ``packed=True`` is distributed SlimSell-B: the batch's frontier/visited
    travel as ``uint32[n_col, ceil(B/32)]`` word planes per shard and the
    iteration all-reduce ORs word vectors (32 roots per lane element, a 32x
    smaller exchange than the lane-boolean batch). Requires
    ``sr_name="boolean"``, ``direction="push"`` and a static ``batch_width``
    (the word-plane geometry is baked into the spec)."""
    if packed:
        check_choice("sr_name", sr_name, ("boolean",),
                     hint="packed=True is the bit-packed boolean push path")
        check_choice("direction", direction, ("push",),
                     hint="the packed sweep is push-only")
        if batch_width is None:
            raise ValueError("packed=True needs a static batch_width "
                             "(the packed plane count is ceil(B/32))")
        spec = packed_multi_bfs_spec(int(batch_width))
    else:
        spec = multi_bfs_spec(sr_name)
    run = make_dist_fixpoint(
        mesh, meta, spec, row_axes=row_axes,
        col_axes=col_axes, max_iters=max_iters, comm=comm, backend=backend,
        direction=direction, slimwork=slimwork,
        finalize=lambda state, iters, dirs: (state["d"].T, iters))
    return lambda *args: run(*args, ())


def make_dist_sssp(mesh: Mesh, meta: DistSlimSell, *,
                   row_axes: Sequence[str] = ("data",),
                   col_axes: Sequence[str] = ("model",),
                   max_iters: int = 512, comm: str = "allreduce",
                   backend: Optional[str] = None, slimwork: bool = False):
    """Jitted distributed delta-stepping SSSP over the weighted partition:
    (cols, row_block, row_vertex[, inc_src, inc_tile], wts, root, delta) ->
    (distances float32[n], sweeps, buckets). ``partition_slimsell`` of a
    weighted CSR supplies the ``wts`` blocks; delta rides as a traced
    operand (same flattened light/heavy phase machine as single-device)."""
    run = make_dist_fixpoint(
        mesh, meta, SSSP_SPEC, row_axes=row_axes, col_axes=col_axes,
        max_iters=max_iters, comm=comm, backend=backend, direction="push",
        slimwork=slimwork,
        finalize=lambda state, iters, dirs:
            (state["dist"], iters, state["buckets"]))

    def fn(*args):
        *head, root, delta = args
        return run(*head, root, (jnp.asarray(delta, jnp.float32),))
    return fn


def make_dist_multi_sssp(mesh: Mesh, meta: DistSlimSell, *,
                         row_axes: Sequence[str] = ("data",),
                         col_axes: Sequence[str] = ("model",),
                         max_iters: int = 512, comm: str = "allreduce",
                         backend: Optional[str] = None,
                         slimwork: bool = False):
    """Jitted distributed batched multi-source SSSP over the column-sharded
    distance matrix: (cols, row_block, row_vertex[, inc_src, inc_tile],
    wts, roots[B], delta) ->
    (distances float32[B, n], iterations, sweeps int32[B], buckets int32[B]).

    One weighted min-plus SpMM per iteration relaxes every root's column;
    the per-column phase machines run replicated (same flattened
    delta-stepping state as ``multi_source_sssp``), so per-root sweeps and
    buckets match the single-device engine exactly. The local sweep feeds
    the raw batch width to the SpMM kernel, which pads widths over 128 to
    whole lane tiles."""
    run = make_dist_fixpoint(
        mesh, meta, MULTI_SSSP_SPEC, row_axes=row_axes, col_axes=col_axes,
        max_iters=max_iters, comm=comm, backend=backend, direction="push",
        slimwork=slimwork,
        finalize=lambda state, iters, dirs:
            (state["dist"].T, iters, state["sweeps"], state["buckets"]))

    def fn(*args):
        *head, roots, delta = args
        return run(*head, roots, (jnp.asarray(delta, jnp.float32),))
    return fn


def make_dist_cc(mesh: Mesh, meta: DistSlimSell, *,
                 row_axes: Sequence[str] = ("data",),
                 col_axes: Sequence[str] = ("model",),
                 max_iters: Optional[int] = None, comm: str = "allreduce",
                 backend: Optional[str] = None, slimwork: bool = False):
    """Jitted distributed connected components (sel-max label propagation):
    (cols, row_block, row_vertex[, inc_src, inc_tile]) ->
    (labels int32[n], iterations); labels[v] = max vertex id of v's
    component."""
    cap = int(max_iters) if max_iters is not None else meta.n + 1
    run = make_dist_fixpoint(
        mesh, meta, CC_SPEC, row_axes=row_axes, col_axes=col_axes,
        max_iters=cap, comm=comm, backend=backend, direction="push",
        slimwork=slimwork,
        finalize=lambda state, iters, dirs:
            (state["x"].astype(jnp.int32) - 1, iters))
    return lambda *args: run(*args, jnp.asarray(0, jnp.int32), ())


def make_dist_pagerank(mesh: Mesh, meta: DistSlimSell, *,
                       row_axes: Sequence[str] = ("data",),
                       col_axes: Sequence[str] = ("model",),
                       max_iters: int = PAGERANK_MAX_ITERS,
                       comm: str = "allreduce",
                       backend: Optional[str] = None,
                       slimwork: bool = False):
    """Jitted distributed PageRank (damped real-semiring power iteration):
    (cols, row_block, row_vertex[, inc_src, inc_tile], damping, tol) ->
    (ranks float32[n], iterations, resid_log float32[WORK_LOG]).

    The per-vertex ``inv_deg`` / ``dangling`` views are built from
    ``meta.deg`` here (the shard-local setup never sees the global degree
    vector) and ride as replicated ctx operands; the L1 residual history in
    ``resid_log`` is what the dist-parity tests compare sweep-for-sweep
    against the single-device engine. ``damping`` / ``tol`` are traced, so
    one compilation serves every parameterization."""
    inv_deg, dangling = pagerank_views(np.asarray(meta.deg))
    run = make_dist_fixpoint(
        mesh, meta, PAGERANK_SPEC, row_axes=row_axes, col_axes=col_axes,
        max_iters=max_iters, comm=comm, backend=backend, direction="push",
        slimwork=slimwork,
        finalize=lambda state, iters, dirs:
            (state["r"], iters, state["resid_log"]))

    def fn(*args):
        *head, damping, tol = args
        ctx_args = (jnp.asarray(damping, jnp.float32),
                    jnp.asarray(tol, jnp.float32), inv_deg, dangling)
        return run(*head, jnp.asarray(0, jnp.int32), ctx_args)
    return fn


def make_dist_brandes(mesh: Mesh, meta: DistSlimSell, *,
                      row_axes: Sequence[str] = ("data",),
                      col_axes: Sequence[str] = ("model",),
                      max_iters: Optional[int] = None,
                      comm: str = "allreduce",
                      backend: Optional[str] = None,
                      slimwork: bool = False):
    """Distributed Brandes betweenness sweeps: (cols, row_block, row_vertex
    [, inc_src, inc_tile], roots[B]) -> (delta float32[n, B], d int32[n, B],
    fwd_iters, bwd_iters).

    Two chained ``make_dist_fixpoint`` runners — the forward sigma/depth
    SpMM batch, then the dependency back-propagation over the recorded
    levels (its ``d`` / ``sigma`` inputs travel as replicated ctx
    operands). Fold the per-source dependency matrix into scores with
    ``betweenness.brandes_accumulate`` (zero the source rows, sum columns,
    halve for the undirected doubling)."""
    cap = int(max_iters) if max_iters is not None else meta.n + 1
    fwd = make_dist_fixpoint(
        mesh, meta, BRANDES_FORWARD_SPEC, row_axes=row_axes,
        col_axes=col_axes, max_iters=cap, comm=comm, backend=backend,
        direction="push", slimwork=slimwork,
        finalize=lambda state, iters, dirs:
            (state["d"], state["sigma"], iters))
    bwd = make_dist_fixpoint(
        mesh, meta, BRANDES_BACKWARD_SPEC, row_axes=row_axes,
        col_axes=col_axes, max_iters=cap, comm=comm, backend=backend,
        direction="push", slimwork=slimwork,
        finalize=lambda state, iters, dirs: (state["delta"], iters))

    def fn(*args):
        *head, roots = args
        d, sigma, it_f = fwd(*head, roots, ())
        levels0 = jnp.max(d, axis=0)        # per-column eccentricity
        delta, it_b = bwd(*head, levels0, (d, sigma))
        return delta, d, it_f, it_b
    return fn


def make_dist_khop(mesh: Mesh, meta: DistSlimSell, k: int, *,
                   row_axes: Sequence[str] = ("data",),
                   col_axes: Sequence[str] = ("model",),
                   comm: str = "allreduce",
                   backend: Optional[str] = None,
                   direction: str = "push", slimwork: bool = False,
                   packed: bool = False,
                   batch_width: Optional[int] = None):
    """Jitted distributed k-hop filter: (cols, row_block, row_vertex
    [, inc_src, inc_tile], roots[B]) -> (distances int32[B, n], iterations)
    with ``distances`` truncated at depth ``k`` (-1 outside the ball; the
    membership mask is ``distances >= 0``).

    A boolean multi-source BFS whose iteration cap *is* the query depth —
    the engine's ``k <= max_iters`` guard does the early exit, so this is
    ``make_dist_multi_bfs`` with ``max_iters=k`` (``packed=True`` for the
    SlimSell-B word-plane exchange)."""
    if k < 0:
        raise ValueError(f"make_dist_khop: k must be >= 0, got {k}")
    return make_dist_multi_bfs(
        mesh, meta, "boolean", row_axes=row_axes, col_axes=col_axes,
        max_iters=int(k), comm=comm, backend=backend, direction=direction,
        slimwork=slimwork, packed=packed, batch_width=batch_width)
