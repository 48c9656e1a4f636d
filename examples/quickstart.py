"""Quickstart: every documented entry point, end to end.

Build a graph, build SlimSell, run algebraic BFS on every semiring and both
execution backends, switch traversal direction with the Beamer heuristic
(``direction="auto"``), batch 8 roots through the multi-source SpMM engine,
run weighted SSSP (delta-stepping over the min-plus semiring) against the
Dijkstra oracle — per-root and batched through the weighted min-plus SpMM
engine — run connected components (sel-max label propagation and boolean
peeling), compare against the traditional oracle, inspect storage, and
serve a mixed BFS/SSSP/CC query stream through a batching GraphSession.

CI executes this script (docs job), so everything the README documents is
exercised here and cannot rot.

    PYTHONPATH=src python examples/quickstart.py
"""
import os

# the distributed demo (step 8) runs on forced host devices; the flag must
# land before jax initializes its backends, and must append to (not clobber
# or defer to) any XLA_FLAGS the environment already carries
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = \
        (_flags + " --xla_force_host_platform_device_count=4").strip()

import numpy as np

from repro.core.bfs import bfs
from repro.core.bfs_traditional import bfs_traditional
from repro.core.cc import cc
from repro.core.formats import build_slimsell, storage_summary
from repro.core.multi_bfs import multi_source_bfs
from repro.core.multi_sssp import multi_source_sssp
from repro.core.options import EngineConfig
from repro.core.sssp import dijkstra_reference, sssp
from repro.graphs.generators import kronecker, with_random_weights


def main():
    # 1. a Graph500-style power-law graph (n = 2^12, ~16 edges/vertex)
    csr = kronecker(scale=12, edge_factor=16, seed=0)
    print(f"graph: n={csr.n} m={csr.m_undirected} max_deg={csr.deg.max()}")

    # 2. SlimSell layout: chunks of C=8 rows, SlimChunk tiles of L=128 cols,
    #    full degree sort (sigma=n). No val array is ever stored.
    tiled = build_slimsell(csr, C=8, L=128, sigma=csr.n).to_jax()
    s = storage_summary(csr, C=8, sigma=csr.n)
    print(f"storage cells: CSR={s.csr} AL={s.al} Sell-C-sigma={s.sell_c_sigma}"
          f" SlimSell={s.slimsell}  (slim/sellcs={s.slimsell_vs_sellcs:.2f})")

    # 3. BFS under all four semirings; sel-max computes parents in-band.
    #    backend="jnp" is the pure-JAX oracle; backend="pallas" runs the
    #    SlimSell TPU kernel engine (interpret mode off-TPU) — identical
    #    distances, SlimWork as scalar-prefetch grid indirection.
    root = int(np.argmax(csr.deg))
    d_ref, _ = bfs_traditional(csr, root)
    for semiring in ("tropical", "real", "boolean", "selmax"):
        res = bfs(tiled, root, semiring, need_parents=True,
                  config=EngineConfig(mode="hostloop"))
        ok = np.array_equal(res.distances, d_ref)
        print(f"{semiring:9s}: iters={res.iterations} "
              f"reached={int((res.distances >= 0).sum())}/{csr.n} "
              f"matches_oracle={ok} "
              f"work/iter={res.work_log.tolist()}")
    print("SlimWork collapses the tail iterations: work/iter above.")

    res_k = bfs(tiled, root, "tropical",
                config=EngineConfig(backend="pallas", mode="fused"))
    print(f"pallas backend matches jnp: "
          f"{np.array_equal(res_k.distances, d_ref)}")

    res_nw = bfs(tiled, root, "tropical", slimwork=False,
                 config=EngineConfig(mode="hostloop"))
    print(f"slimwork=False (every tile, every iter) still matches: "
          f"{np.array_equal(res_nw.distances, d_ref)} "
          f"work/iter={res_nw.work_log.tolist()}")

    # 4. direction-optimizing traversal (paper §V / Beamer): "push" expands
    #    the frontier top-down, "pull" sweeps the unexplored rows bottom-up
    #    (early-exit per row in the pallas kernel), "auto" switches per
    #    iteration on the alpha/beta heuristic — fewest tiles touched overall
    for direction in ("push", "pull", "auto"):
        res = bfs(tiled, root, "tropical", log_work=True,
                  config=EngineConfig(mode="hostloop", direction=direction))
        ok = np.array_equal(res.distances, d_ref)
        print(f"direction={direction:4s}: tiles/iter={res.work_log.tolist()} "
              f"total={int(res.work_log.sum())} "
              f"dirs={res.directions.tolist()} matches_oracle={ok}")

    # 5. batched multi-source BFS (Graph500's 64-root harness uses this):
    #    8 roots advance together through one semiring SpMM per iteration
    #    direction="auto" gives every root column its own push/pull state
    roots = np.random.default_rng(0).choice(
        np.nonzero(csr.deg > 0)[0], 8, replace=False)
    ms = multi_source_bfs(tiled, roots, "tropical", batch_size=8,
                          config=EngineConfig(direction="auto"))
    ok = all(np.array_equal(ms.distances[i], bfs_traditional(csr, int(r))[0])
             for i, r in enumerate(roots))
    print(f"multi-source: {len(roots)} roots in "
          f"{int(ms.iterations.max())} iters/batch, matches_oracle={ok}")

    # 6. weighted SSSP: delta-stepping over the min-plus semiring. The same
    #    layout builder carries a per-slot weight array (SlimSell-W) when the
    #    CSR is weighted; light/heavy relaxations are min-plus SpMV sweeps on
    #    the same engine (fused nested while_loops, or hostloop with SlimWork
    #    tile gathering), and delta=inf degenerates to Bellman-Ford.
    wcsr = with_random_weights(csr, low=0.25, high=2.0, seed=1)
    wtiled = build_slimsell(wcsr, C=8, L=128).to_jax()
    sp_ref = dijkstra_reference(wcsr, root)
    for mode, backend in (("fused", "jnp"), ("fused", "pallas"),
                          ("hostloop", "jnp")):
        res = sssp(wtiled, root, need_parents=True,
                   config=EngineConfig(mode=mode, backend=backend))
        ok = np.allclose(res.distances, sp_ref, rtol=1e-4, atol=1e-5)
        print(f"sssp {mode:8s}/{backend:6s}: sweeps={res.sweeps} "
              f"buckets={res.buckets} delta={res.delta:.3f} "
              f"matches_dijkstra={ok}")
    delta_default = res.delta  # the mean-edge-weight default, for step 8
    bf = sssp(wtiled, root, delta=np.inf)  # Bellman-Ford: one bucket
    print(f"sssp delta=inf (Bellman-Ford): buckets={bf.buckets} "
          f"sweeps={bf.sweeps} matches_dijkstra="
          f"{np.allclose(bf.distances, sp_ref, rtol=1e-4, atol=1e-5)}")

    # 7. connected components: sel-max label propagation runs the fixpoint
    #    x' = max(x, A x) until no label changes (labels = max vertex id per
    #    component); boolean peeling runs one boolean BFS per component.
    res_lp = cc(tiled, semiring="selmax", config=EngineConfig(mode="fused"))
    res_bp = cc(tiled, semiring="boolean",
                config=EngineConfig(mode="hostloop"))
    print(f"cc: {res_lp.n_components} components in {res_lp.iterations} "
          f"label-prop sweeps; boolean peeling agrees="
          f"{np.array_equal(res_lp.labels, res_bp.labels)}")

    # 8. the same specs over a 2D device mesh (here 2x2 forced host devices):
    #    rows x columns of the adjacency sharded over ("data", "model"), one
    #    semiring all-reduce per iteration; bfs/multi/sssp/multi-sssp/cc all
    #    come from the shared engine's distributed strategy.
    import jax
    import jax.numpy as jnp
    from repro.core.dist_bfs import make_mesh
    if jax.local_device_count() < 4:
        # the XLA flag only grows the *cpu* platform; on a 1-GPU/TPU default
        # backend there is no 2x2 mesh to build — skip the demo, don't crash
        print(f"dist demo skipped: {jax.local_device_count()} device(s) on "
              f"backend={jax.default_backend()} (needs 4; run on CPU)")
    else:
        from repro.core.dist_bfs import (make_dist_bfs, make_dist_cc,
                                         make_dist_multi_bfs,
                                         make_dist_multi_sssp, make_dist_sssp,
                                         partition_slimsell)
        mesh = make_mesh((2, 2), ("data", "model"))
        dist = partition_slimsell(csr, R=2, Co=2, C=8, L=128)
        dfn = make_dist_bfs(mesh, dist, "tropical", max_iters=64,
                            direction="auto")
        d, iters = dfn(dist.cols, dist.row_block, dist.row_vertex,
                       jnp.asarray(dist.deg, jnp.int32), np.int32(root))
        print(f"dist bfs (2x2 mesh, auto): iters={int(iters)} "
              f"matches_oracle={np.array_equal(np.asarray(d), d_ref)}")
        mfn = make_dist_multi_bfs(mesh, dist, "selmax", max_iters=64,
                                  direction="pull")
        md, _ = mfn(dist.cols, dist.row_block, dist.row_vertex,
                    roots.astype(np.int32))
        ok = all(np.array_equal(np.asarray(md)[i],
                                bfs_traditional(csr, int(r))[0])
                 for i, r in enumerate(roots))
        print(f"dist multi-source (pull): {len(roots)} roots, "
              f"matches_oracle={ok}")
        wdist = partition_slimsell(wcsr, R=2, Co=2, C=8, L=128)
        sfn = make_dist_sssp(mesh, wdist, max_iters=512)
        # the mean-edge-weight default from step 6, so the mesh run exercises
        # real multi-bucket delta-stepping (bf.delta is inf == Bellman-Ford)
        sd, sweeps, buckets = sfn(wdist.cols, wdist.row_block,
                                  wdist.row_vertex, wdist.wts, np.int32(root),
                                  np.float32(delta_default))
        print(f"dist sssp: sweeps={int(sweeps)} buckets={int(buckets)} "
              f"matches_dijkstra="
              f"{np.allclose(np.asarray(sd), sp_ref, rtol=1e-4, atol=1e-5)}")
        msfn = make_dist_multi_sssp(mesh, wdist, max_iters=512)
        msd, _, msweeps, _ = msfn(wdist.cols, wdist.row_block,
                                  wdist.row_vertex, wdist.wts,
                                  roots[:4].astype(np.int32),
                                  np.float32(delta_default))
        ok = all(np.allclose(np.asarray(msd)[i],
                             dijkstra_reference(wcsr, int(r)),
                             rtol=1e-4, atol=1e-5)
                 for i, r in enumerate(roots[:4]))
        print(f"dist multi-source sssp: {len(roots[:4])} roots over the "
              f"column-sharded distance matrix, matches_dijkstra={ok}")
        cfn = make_dist_cc(mesh, dist)
        labels, _ = cfn(dist.cols, dist.row_block, dist.row_vertex)
        print(f"dist cc: matches_single_device="
              f"{np.array_equal(np.asarray(labels), res_lp.labels)}")

    # 9. batched multi-source SSSP: B roots' distance columns advance
    #    together through one weighted min-plus SpMM per relaxation sweep
    #    (core.multi_sssp) — the weighted twin of step 5's SpMM batching,
    #    with per-column delta buckets and union SlimWork tile masks. The
    #    per-root sweeps/buckets match the per-root engine of step 6
    #    exactly, on both backends (the pallas kernel's wts block shares the
    #    cols block's scalar-prefetch indirection).
    sp_refs = [dijkstra_reference(wcsr, int(r)) for r in roots]
    for backend in ("jnp", "pallas"):
        ms = multi_source_sssp(wtiled, roots,
                               config=EngineConfig(backend=backend))
        ok = all(np.allclose(ms.distances[i], sp_refs[i],
                             rtol=1e-4, atol=1e-5)
                 for i in range(len(roots)))
        print(f"multi-source sssp/{backend:6s}: {len(roots)} roots in "
              f"{int(ms.iterations.max())} batch sweeps "
              f"(per-root sweeps={ms.sweeps.tolist()}), "
              f"matches_dijkstra={ok}")

    # 10. the serving layer: a GraphSession keeps the layout resident and
    #     batches a heterogeneous query stream by (algorithm, semiring,
    #     delta) onto cached jitted engine handles — every answer bit-equal
    #     to the one-shot calls above. EngineConfig is the knob carrier the
    #     front doors share with the session.
    from repro.serving import GraphSession
    sess = GraphSession(wtiled, config=EngineConfig(backend="jnp"),
                        max_batch=8)
    handles = [sess.submit("bfs", int(r)) for r in roots[:4]]
    handles += [sess.submit("sssp", int(r)) for r in roots[:4]]
    handles.append(sess.submit("cc"))
    sess.drain()
    ok = all(np.array_equal(h.result().distances,
                            bfs_traditional(csr, int(r))[0])
             for h, r in zip(handles[:4], roots[:4]))
    ok &= all(np.allclose(h.result().distances, sp_refs[i],
                          rtol=1e-4, atol=1e-5)
              for i, h in enumerate(handles[4:8]))
    ok &= handles[8].result().n_components == res_lp.n_components
    st = sess.stats()
    print(f"serving: {st['completed']} mixed queries in "
          f"{st['batches_dispatched']} batches "
          f"(fill={st['batch_fill_ratio']:.2f}, "
          f"compile misses={st['compile_cache_misses']}), "
          f"matches_per_call={ok}")
    expired = sess.submit("bfs", root, deadline=0.0)
    sess.flush()
    print(f"serving deadline: status={expired.result().status!r} "
          f"(typed DeadlineExpired on access)")


if __name__ == "__main__":
    main()
