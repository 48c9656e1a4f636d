#!/usr/bin/env python3
"""Smoke run of the main path on a TPU, through the entry points a user calls.

    python chip_smoke.py                 # one chip: Graph500, Pallas, served
    python chip_smoke.py --four-chips    # four chips: the 2D partition only

One chip, in one process, in phases:

1. device — fails unless JAX's first device is a TPU; refuses to start when
   ``REPRO_PALLAS_INTERPRET`` forces Pallas interpret mode.
2. graph500 — a Kronecker graph (edge factor 16, the
   ``examples/graph500_driver.py`` layout: C=8, L=128, sigma=n) stays
   resident; ``run_graph500`` runs 8 roots in one batch on the jnp backend,
   push and auto, and validates every tree. The scale is cut from
   Graph500 scale 22 to 20 (``DEFAULT_SCALE``), the largest that ends
   within the run's time limit; the cut is printed.
3. pallas — ``bfs`` single-source through ``backend="pallas"`` on the
   resident graph, and ``run_graph500`` through ``backend="pallas"`` at the
   largest scale the batched kernels' VMEM bound admits; results must equal
   the jnp path's, and one compiled sweep must hold a Mosaic kernel.
4. served — a ``GraphSession`` over the resident (weighted) layout answers
   a mixed ``submit``/``flush`` stream checked against ``bfs_traditional``,
   ``dijkstra_reference`` and scipy's connected components.

``--four-chips`` runs only the 2D partition (``partition_slimsell`` R=2,
Co=2 on a (2, 2) mesh): BFS and multi-source BFS compared with the
single-device engine and the oracle, with each device's bytes in use.

Any failed check raises and exits non-zero. The last line of standard output
is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
N_ROOTS = 8
#: Graph500 scale of the one-chip run. Scale 22 does not fit the 1200 s a
#: smoke run may take: at scale 20 the whole run took 702 s on one v5e
#: chip, and host build and device phases grow about 4x per two scales.
DEFAULT_SCALE = 20
#: the four-chip run compares small graphs: four chips cost four times the
#: chip time of one
FOUR_CHIP_SCALE = 18


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def bound_scale(name: str, kernel, make_args, lo: int = 10,
                hi: int = 30) -> int:
    """Largest Graph500 scale whose frontier ``kernel`` admits under its
    VMEM bound, found by tracing the real wrapper on shapes only."""
    import jax
    from repro.kernels.sweep import VmemBoundError
    best = None
    for scale in range(lo, hi):
        try:
            jax.eval_shape(kernel, *make_args(1 << scale))
        except VmemBoundError:
            break
        best = scale
    check(best is not None, f"{name} admits no scale >= {lo}")
    return best


def device_memory(when: str) -> None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    log(f"[device] {when}: bytes_in_use={stats.get('bytes_in_use')} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")


def in_use_bytes(devices):
    return [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]


def phase_device(args):
    interp = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    if interp in ("1", "true", "yes"):
        raise SystemExit("chip_smoke: REPRO_PALLAS_INTERPRET forces Pallas "
                         "interpret mode; refusing to start")
    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform}")
    want = 4 if args.four_chips else 1
    check(len(devices) >= want, f"needs {want} TPU devices, found "
          f"{len(devices)}")
    return dev, len(devices)


def build_graph(scale: int):
    """Host build of the resident graph: Kronecker CSR, symmetric weights
    for SSSP, and the weighted SlimSell layout (cols shared by every
    unweighted query)."""
    import numpy as np
    from repro.core.formats import build_slimsell
    from repro.graphs.generators import kronecker, with_random_weights
    t0 = time.perf_counter()
    csr = kronecker(scale, 16, seed=1)
    t1 = time.perf_counter()
    wcsr = with_random_weights(csr, seed=1)
    t2 = time.perf_counter()
    host = build_slimsell(wcsr, C=8, L=128, sigma=wcsr.n)
    t3 = time.perf_counter()
    log(f"[graph500] scale={scale} ef=16 n={csr.n} nnz={csr.nnz} "
        f"isolated={int((csr.deg == 0).sum())}")
    log(f"[graph500] host build s: kronecker={t1 - t0:.1f} "
        f"weights={t2 - t1:.1f} slimsell={t3 - t2:.1f}")
    log(f"[graph500] layout: tiles={host.n_tiles} chunks={host.n_chunks} "
        f"cols_bytes={host.cols.nbytes} wts_bytes={host.wts.nbytes} "
        f"push_pairs={host.inc_src.size} "
        f"slots_per_nnz={host.cols.size / max(csr.nnz, 1):.2f}")
    t0 = time.perf_counter()
    tiled = host.to_jax()
    np.asarray(tiled.cols[0, 0, :1])      # wait for the transfer
    log(f"[graph500] device put s: {time.perf_counter() - t0:.1f}")
    return csr, wcsr, tiled


def phase_graph500(scale: int, csr, tiled):
    from repro.graph500 import run_graph500
    for direction in ("push", "auto"):
        t0 = time.perf_counter()
        rep = run_graph500(scale=scale, edge_factor=16, n_roots=N_ROOTS,
                           batch_size=N_ROOTS, backend="jnp",
                           direction=direction, csr=csr, tiled=tiled)
        log(f"[graph500] {rep.summary()} wall_s={time.perf_counter() - t0:.1f}"
            f" batch_s={rep.batch_seconds.tolist()}")
        check(rep.validated == N_ROOTS,
              f"graph500 {direction}: {rep.validated}/{N_ROOTS} validated")
        log(f"[graph500] {direction}: {rep.validated}/{N_ROOTS} trees "
            f"validated")


def phase_pallas(scale: int, csr, tiled):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.bfs import bfs
    from repro.core.formats import build_slimsell
    from repro.core.multi_bfs import multi_source_bfs
    from repro.core.options import EngineConfig
    from repro.graph500 import run_graph500, sample_roots
    from repro.graphs.generators import kronecker
    from repro.kernels import ops
    from repro.kernels.slimsell_spmm import slimsell_spmm_pallas
    from repro.kernels.slimsell_spmv import slimsell_spmv_pallas

    S = jax.ShapeDtypeStruct
    tile = (S((8, 8, 128), jnp.int32), S((8,), jnp.int32),
            S((8,), jnp.int32), S((1,), jnp.int32))
    spmv_scale = bound_scale(
        "spmv",
        lambda *a: slimsell_spmv_pallas(*a, sr_name="tropical", n_chunks=8,
                                        interpret=False),
        lambda n: tile + (S((n,), jnp.float32),))
    spmm_scale = bound_scale(
        "spmm",
        lambda *a: slimsell_spmm_pallas(*a, sr_name="tropical", n_chunks=8,
                                        interpret=False),
        lambda n: tile + (S((n, N_ROOTS), jnp.float32),))
    log(f"[pallas] VMEM bound admits: spmv/pull up to scale {spmv_scale}, "
        f"spmm/pull-mm ({N_ROOTS} roots) up to scale {spmm_scale}")

    text = ops.spmv.lower("tropical", tiled,
                          jnp.zeros((tiled.n,), jnp.float32)
                          ).compile().as_text()
    check("tpu_custom_call" in text, "compiled pallas sweep holds no Mosaic "
          "kernel (tpu_custom_call)")
    log("[pallas] compiled sweep holds tpu_custom_call: True")

    # single-source: the resident graph, if the bound admits its scale
    s1 = min(scale, spmv_scale)
    if s1 < scale:
        log(f"[pallas] single-source cut to scale {s1} (VMEM bound)")
        c1 = kronecker(s1, 16, seed=1)
        t1 = build_slimsell(c1, C=8, L=128, sigma=c1.n).to_jax()
    else:
        c1, t1 = csr, tiled
    root = int(sample_roots(c1, 1)[0])
    for direction in ("push", "auto"):
        got = {}
        for backend in ("jnp", "pallas"):
            t0 = time.perf_counter()
            res = bfs(t1, root, config=EngineConfig(backend=backend,
                                                    direction=direction))
            got[backend] = np.asarray(res.distances)
            log(f"[pallas] bfs scale={s1} root={root} {direction} "
                f"{backend}: iters={res.iterations} "
                f"reached={int((got[backend] >= 0).sum())} "
                f"s={time.perf_counter() - t0:.2f}")
        check(np.array_equal(got["jnp"], got["pallas"]),
              f"pallas bfs ({direction}) differs from jnp")
        log(f"[pallas] bfs {direction} pallas == jnp: True")

    # batched: run_graph500 through the SpMM / pull-MM kernels
    s2 = min(scale, spmm_scale)
    c2 = kronecker(s2, 16, seed=1)
    t2 = build_slimsell(c2, C=8, L=128, sigma=c2.n).to_jax()
    roots = sample_roots(c2, N_ROOTS)
    for direction in ("push", "auto"):
        rep = run_graph500(scale=s2, edge_factor=16, n_roots=N_ROOTS,
                           batch_size=N_ROOTS, backend="pallas",
                           direction=direction, csr=c2, tiled=t2)
        log(f"[pallas] {rep.summary()}")
        check(rep.validated == N_ROOTS,
              f"pallas graph500 {direction}: {rep.validated}/{N_ROOTS}")
        d = {b: multi_source_bfs(t2, roots, config=EngineConfig(
            backend=b, direction=direction)).distances
            for b in ("jnp", "pallas")}
        check(np.array_equal(d["jnp"], d["pallas"]),
              f"pallas multi-source bfs ({direction}) differs from jnp")
        log(f"[pallas] graph500 scale={s2} {direction}: "
            f"{rep.validated}/{N_ROOTS} validated, pallas == jnp: True")


def phase_served(csr, wcsr, tiled, sssp_roots, sssp_refs):
    import numpy as np
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components
    from repro.core.bfs_traditional import bfs_traditional
    from repro.core.options import EngineConfig
    from repro.graph500 import sample_roots, validate_sssp_tree
    from repro.serving import GraphSession

    bfs_roots = [int(r) for r in sample_roots(csr, 4, seed=7)]
    khop_root = bfs_roots.pop()
    sess = GraphSession(tiled, config=EngineConfig(), max_batch=N_ROOTS)
    t0 = time.perf_counter()
    handles = [("bfs", r, sess.submit("bfs", r)) for r in bfs_roots]
    handles += [("sssp", r, sess.submit("sssp", r)) for r in sssp_roots]
    handles.append(("cc", None, sess.submit("cc")))
    handles.append(("khop", khop_root, sess.submit("khop", khop_root, k=2)))
    sess.flush()
    results = [(kind, r, h.result()) for kind, r, h in handles]
    st = sess.stats()
    log(f"[served] {len(results)} queries answered in "
        f"{time.perf_counter() - t0:.1f}s: completed={st['completed']} "
        f"batches={st['batches_dispatched']} "
        f"fill={st['batch_fill_ratio']:.2f} "
        f"p99_ms={st['latency_p99_ms']:.1f}")
    for kind, r, res in results:
        check(res.status == "ok", f"served {kind}({r}) status={res.status}")
        if kind == "bfs":
            ok = np.array_equal(res.values, bfs_traditional(csr, r)[0])
        elif kind == "khop":
            ref = bfs_traditional(csr, r)[0]
            ok = np.array_equal(res.values, np.where(ref <= 2, ref, -1))
        elif kind == "sssp":
            validate_sssp_tree(wcsr, r, res.values,
                               d_ref=sssp_refs[r].result())
            ok = True
        else:
            adj = sp.csr_matrix((np.ones(csr.nnz, np.int8), csr.indices,
                                 csr.indptr), shape=(csr.n, csr.n))
            n_ref, lab_ref = connected_components(adj, directed=False)
            pairs = np.unique(np.stack([res.values, lab_ref]), axis=1)
            ok = res.n_components == n_ref == pairs.shape[1]
        check(ok, f"served {kind}({r}) disagrees with its oracle")
        log(f"[served] {kind}({r}) sweeps={res.sweeps} matches oracle: True")
    sess.close()


def phase_four_chips(scale: int):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.bfs import bfs
    from repro.core.bfs_traditional import bfs_traditional
    from repro.core.dist_bfs import (make_dist_bfs, make_dist_multi_bfs,
                                     make_mesh, partition_slimsell)
    from repro.core.formats import build_slimsell
    from repro.core.multi_bfs import multi_source_bfs
    from repro.graph500 import sample_roots
    from repro.graphs.generators import kronecker

    t0 = time.perf_counter()
    csr = kronecker(scale, 16, seed=1)
    dist = partition_slimsell(csr, R=2, Co=2)
    log(f"[four-chips] scale={scale} n={csr.n} nnz={csr.nnz} "
        f"shard cols={dist.cols.shape} host build "
        f"s={time.perf_counter() - t0:.1f}")
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))  # noqa: E731
    cols = put(dist.cols, P("data", "model", None, None, None))
    row_block = put(dist.row_block, P("data", "model", None))
    row_vertex = put(dist.row_vertex, P("data", None, None))
    jax.block_until_ready((cols, row_block, row_vertex))
    devices = list(mesh.devices.flat)
    held = [sum(sh.data.nbytes for sh in cols.addressable_shards
                if sh.device == d) for d in devices]
    log(f"[four-chips] cols shard bytes per device: {held}; bytes in use "
        f"per device: {in_use_bytes(devices)}")
    check(all(b == dist.cols.nbytes // 4 for b in held),
          f"cols shards not spread over all four chips: {held}")
    roots = sample_roots(csr, N_ROOTS)
    single = build_slimsell(csr, C=8, L=128).to_jax()
    fn = make_dist_bfs(mesh, dist, "tropical", max_iters=csr.n)
    for r in roots[:2]:
        d, iters = fn(cols, row_block, row_vertex, np.int32(r))
        ref = bfs_traditional(csr, int(r))[0]
        one = np.asarray(bfs(single, int(r)).distances)
        check(np.array_equal(np.asarray(d), ref)
              and np.array_equal(one, ref),
              f"2D bfs root {r} disagrees with the single-device engine or "
              f"the oracle")
        log(f"[four-chips] bfs root={r} iters={int(iters)}: 2D == single "
            f"device == oracle")
    mfn = make_dist_multi_bfs(mesh, dist, "tropical", max_iters=csr.n)
    t0 = time.perf_counter()
    md, iters = mfn(cols, row_block, row_vertex, roots)
    md = np.asarray(md)
    log(f"[four-chips] multi-source {N_ROOTS} roots iters={int(iters)} "
        f"s={time.perf_counter() - t0:.1f}")
    one = multi_source_bfs(single, roots).distances
    refs = np.stack([bfs_traditional(csr, int(r))[0] for r in roots])
    check(np.array_equal(md, refs) and np.array_equal(one, refs),
          "2D multi-source bfs disagrees with the single-device engine or "
          "the oracle")
    log(f"[four-chips] multi-source: 2D == single device == oracle "
        f"({N_ROOTS} roots)")
    log(f"[four-chips] bytes in use per device after runs: "
        f"{in_use_bytes(devices)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2D-partition phase on four chips")
    ap.add_argument("--scale", type=int, default=None,
                    help=f"Kronecker scale (default {DEFAULT_SCALE}; "
                         f"{FOUR_CHIP_SCALE} with --four-chips)")
    args = ap.parse_args(argv)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repro package under {src}")
    sys.path.insert(0, str(src))
    from repro.compile_cache import use_compile_cache
    cache = use_compile_cache()

    dev, count = phase_device(args)
    log(f"[device] compile cache: {cache}")
    t_start = time.perf_counter()
    if args.four_chips:
        phase_four_chips(args.scale or FOUR_CHIP_SCALE)
    else:
        from repro.core.sssp import dijkstra_reference
        from repro.graph500 import sample_roots
        scale = args.scale or DEFAULT_SCALE
        if scale != 22:
            log(f"[graph500] scale cut from 22 to {scale}: "
                + ("the run must end within 1200 s" if args.scale is None
                   else "--scale"))
        csr, wcsr, tiled = build_graph(scale)
        # the host Dijkstra oracle is slow at this size: it runs beside
        # the device phases and is read in the served phase
        sssp_roots = [int(r) for r in sample_roots(csr, 2, seed=11)]
        with concurrent.futures.ThreadPoolExecutor(1) as pool:
            sssp_refs = {r: pool.submit(dijkstra_reference, wcsr, r)
                         for r in sssp_roots}
            device_memory("graph built")
            phase_graph500(scale, csr, tiled)
            device_memory("graph500 done")
            phase_pallas(scale, csr, tiled)
            device_memory("pallas done")
            phase_served(csr, wcsr, tiled, sssp_roots, sssp_refs)
            device_memory("served done")
    log(f"[device] phases took {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
