import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile one (arch x shape x mesh) cell.

    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
        --shape train_4k [--multi-pod] [--out results/dryrun]

Succeeding means: the 512-placeholder-device mesh builds, every input has a
coherent sharding, GSPMD partitions the step, and XLA compiles it. The
printed memory_analysis proves per-chip fit; cost_analysis + the HLO
collective parse feed EXPERIMENTS.md §Roofline.
"""
import argparse
import json
import time
import traceback


def _analyze(compiled):
    from repro.launch.roofline import collective_bytes
    cost_list = compiled.cost_analysis()
    cost = cost_list if isinstance(cost_list, dict) else (
        cost_list[0] if cost_list else {})
    coll = collective_bytes(compiled.as_text())
    return ({"flops": float(cost.get("flops", 0.0)),
             "bytes accessed": float(cost.get("bytes accessed", 0.0))}, coll)


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str | None,
             save_hlo: bool = False) -> dict:
    import jax
    from repro.configs import build_cell, get as get_arch
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import collective_bytes, roofline

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    rec = {"arch": arch, "shape": shape,
           "mesh": "x".join(str(s) for s in mesh.devices.shape),
           "n_devices": n_dev, "status": "error"}
    family = get_arch(arch).FAMILY
    try:
        cell = build_cell(arch, shape, mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(cell.fn).lower(*cell.args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
        mem = compiled.memory_analysis()
        cost, coll = _analyze(compiled)
        hlo = compiled.as_text()

        # XLA cost analysis counts while/scan bodies ONCE. Correction:
        #  * lm: lower 1- and 2-layer variants with UNROLLED layer scans and
        #    single-trip attention scans (no while ops left in the layer
        #    stack); layers are identical => cost(L) = c1 + (L-1)(c2-c1).
        #  * bfs: the while body is one BFS iteration; the direct numbers ARE
        #    per-iteration. A full run is ~D_est iterations.
        #  * gnn/recsys: no scans; direct numbers are exact.
        method = "direct"
        if family == "lm":
            method = "layer-extrapolation"
            L = get_arch(arch).make_config().n_layers
            with jax.set_mesh(mesh):
                c1 = build_cell(arch, shape, mesh, cost_layers=1)
                comp1 = jax.jit(c1.fn).lower(*c1.args).compile()
                cost1, coll1 = _analyze(comp1)
                c2 = build_cell(arch, shape, mesh, cost_layers=2)
                comp2 = jax.jit(c2.fn).lower(*c2.args).compile()
                cost2, coll2 = _analyze(comp2)
            cost = {k: cost1[k] + (L - 1) * (cost2[k] - cost1[k])
                    for k in ("flops", "bytes accessed")}
            coll = {k: coll1.get(k, 0) + (L - 1) * (coll2.get(k, 0)
                                                    - coll1.get(k, 0))
                    for k in set(coll1) | set(coll2)}
        elif family == "bfs":
            method = "per-iteration(while-body-once)"

        rl = roofline(cost, coll, n_dev, cell.model_flops
                      if family != "bfs" else cell.model_flops / 12)
        rec.update(
            status="ok",
            kind=cell.kind,
            cost_method=method,
            lower_s=round(t_lower - t0, 1),
            compile_s=round(t_compile - t_lower, 1),
            memory={
                "argument_gib": getattr(mem, "argument_size_in_bytes", 0) / 2**30,
                "output_gib": getattr(mem, "output_size_in_bytes", 0) / 2**30,
                "temp_gib": getattr(mem, "temp_size_in_bytes", 0) / 2**30,
                "peak_gib": (getattr(mem, "argument_size_in_bytes", 0)
                             + getattr(mem, "temp_size_in_bytes", 0)) / 2**30,
            },
            cost=cost,
            collectives=coll,
            roofline=rl,
        )
        if save_hlo and out_dir:
            tag = "mp" if multi_pod else "sp"
            with open(os.path.join(out_dir, f"{arch}__{shape}__{tag}.hlo"),
                      "w") as f:
                f.write(hlo)
    except Exception as e:  # recorded, not raised: the matrix runner reports
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 1)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = "mp" if multi_pod else "sp"
        with open(os.path.join(out_dir, f"{arch}__{shape}__{tag}.json"),
                  "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()
    rec = run_cell(args.arch, args.shape, args.multi_pod, args.out,
                   args.save_hlo)
    drop = rec.pop("traceback", None)
    print(json.dumps(rec, indent=1))
    if drop:
        print(drop)
    raise SystemExit(0 if rec["status"] == "ok" else 1)


if __name__ == "__main__":
    main()
