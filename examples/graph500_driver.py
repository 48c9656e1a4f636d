"""End-to-end driver (the paper's workload): Graph500-style BFS benchmark.

Builds a Kronecker graph, runs BFS from the spec's 64 sampled roots in
*batches* through the multi-source semiring-SpMM engine, validates every
tree against the queue-based oracle, and reports harmonic-mean TEPS — the
Graph500 metric. ``--backend pallas`` routes every sweep through the SlimSell
Pallas kernels (interpret mode off-TPU). With >1 device it also runs the
2D-distributed engine.

    PYTHONPATH=src python examples/graph500_driver.py --scale 13 --ef 16 \
        --roots 64 --batch 16 --backend pallas
"""
import argparse
import time

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.bfs import bfs
from repro.core.bfs_traditional import bfs_traditional
from repro.core.formats import build_slimsell
from repro.graph500 import run_graph500
from repro.graphs.generators import kronecker


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=13)
    ap.add_argument("--ef", type=int, default=16)
    ap.add_argument("--roots", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--semiring", default="tropical")
    ap.add_argument("--backend", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--no-validate", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    t0 = time.time()
    csr = kronecker(args.scale, args.ef, seed=1)
    tiled = build_slimsell(csr, C=8, L=128, sigma=csr.n).to_jax()
    print(f"built n={csr.n} m={csr.m_undirected} in {time.time()-t0:.1f}s "
          f"(amortized over {args.roots} BFS runs, paper §IV-D)")

    rep = run_graph500(scale=args.scale, edge_factor=args.ef,
                       n_roots=args.roots, batch_size=args.batch,
                       semiring=args.semiring, backend=args.backend,
                       validate=not args.no_validate, csr=csr, tiled=tiled)
    print(rep.summary())

    if len(jax.devices()) < 4:
        print(f"distributed 2D BFS skipped: {len(jax.devices())} "
              f"{jax.devices()[0].platform} device(s), needs 4")
    else:
        from repro.core.dist_bfs import (make_dist_bfs, make_mesh,
                                         partition_slimsell)
        mesh = make_mesh((2, 2), ("data", "model"))
        dist = partition_slimsell(csr, R=2, Co=2)
        fn = make_dist_bfs(mesh, dist, args.semiring, backend=args.backend)
        root = int(rep.roots[0])
        d, iters = fn(dist.cols, dist.row_block, dist.row_vertex,
                      np.int32(root))
        d_ref, _ = bfs_traditional(csr, root)
        print("distributed 2D BFS matches:",
              np.array_equal(np.asarray(d), d_ref), f"iters={int(iters)}")


if __name__ == "__main__":
    main()
