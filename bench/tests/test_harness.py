"""The harness on the CPU at a tiny scale: cells, metrics and traffic are
found by name, each cell runs and proves correct, a broken timed path is
caught, and a run without a chip is refused."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness

SECONDS = 2.0
CELLS = ("g500-bfs", "g500-sssp", "svc-khop1")


def run(root, workload, seed=2**33 + 7):
    return harness.run_cell(workload, seed, SECONDS, False,
                            require_tpu=False, root=root)


def test_new_traffic_and_metric_files_are_found_by_name(tiny_root):
    """A later change adds a cell and a metric by adding files and entries;
    no file that is there changes."""
    before = {p: p.read_bytes() for p in (tiny_root / "bench").rglob("*")
              if p.is_file()}
    (tiny_root / "bench" / "traffic" / "snb-mixed.json").write_text(
        json.dumps({"kind": "open_loop", "rate_qps": 8.0,
                    "mix": [{"algorithm": "khop", "k": 2, "share": 0.5},
                            {"algorithm": "bfs", "share": 0.3},
                            {"algorithm": "sssp", "share": 0.2}],
                    "check_per_type": 2}))
    (tiny_root / "bench" / "metrics" / "answered_qps.py").write_text(
        "def read(run):\n"
        "    return run.window['completed'] / run.window['elapsed_s']\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "svc-mixed", "config": "snb-svc-kron",
                              "traffic": "snb-mixed", "chips": 1,
                              "why": "k-hop, BFS and SSSP reads"})
    spec["end_to_end"].append({"name": "answered_qps", "unit": "1/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["svc-mixed"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    result = run(tiny_root, "svc-mixed")
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"answered_qps", "setup_s"}
    assert {"level_mismatch", "dist_gap", "reach_mismatch"} <= \
        set(result["checks"])
    assert all((tiny_root / "bench" / p.relative_to(tiny_root / "bench"))
               .read_bytes() == data for p, data in before.items())


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(tiny_root, workload):
    result = run(tiny_root, workload)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in harness.metrics_for(spec, "end_to_end",
                                                   workload)}
    assert set(result["metrics"]) == want
    assert list(result)[-1] == "checks"
    line = harness.dumps(result)
    assert json.loads(line)["correct"] is True


@pytest.mark.parametrize("workload", ("g500-bfs", "g500-sssp"))
def test_every_batch_is_checked_on_the_control_s_sample(tiny_root,
                                                        monkeypatch,
                                                        workload):
    """A run checks ``check_sample`` distinct columns of every batch; the
    control answers the same columns of the window's first batch."""
    from bench import checks, control, gen
    checked, answered = [], []
    real = checks.compare_batches

    def compare_batches(run, g, kept):
        checked.extend(root for root, _ in kept)
        return real(run, g, kept)
    monkeypatch.setattr(checks, "compare_batches", compare_batches)
    seed = 2**33 + 7
    result = run(tiny_root, workload, seed)
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    _, config, traffic = harness.find_cell(spec, workload, tiny_root)
    take, width = traffic["check_sample"], traffic["batch"]
    batches = result["attempted"] // width
    assert len(checked) == take * batches
    assert all(len(set(checked[i:i + take])) == take
               for i in range(0, len(checked), take))
    real_answer = control.control_answer

    def control_answer(g, entry, root):
        answered.append(root)
        return real_answer(g, entry, root)
    monkeypatch.setattr(control, "control_answer", control_answer)
    control.control_numbers(gen.from_config(config), config, traffic, seed,
                            SECONDS)
    assert answered == checked[:take]


# ---------------------------------------------------------- front door


def _config(root, name="g500-kron", **layout):
    config = json.loads((root / "bench" / "configs" / f"{name}.json")
                        .read_text())
    config["layout"].update(layout)
    return config


def _build(config):
    run_ = harness.Run(workload="g500-bfs", kind="batches", seed=1,
                       seconds=SECONDS, cell={}, config=config, traffic={})
    return run_, harness.build(run_)


@pytest.mark.parametrize("weighted", (True, False))
def test_front_door_layout_equals_the_harness_s_own_build(tiny_root,
                                                          weighted):
    """The session's layout, built from the CSR, is the one the harness
    built itself with ``build_slimsell(csr, C, L, sigma=n).to_jax()``."""
    from repro.core.formats import CSRGraph, build_slimsell
    config = _config(tiny_root)
    if not weighted:
        for key in ("weight_low", "weight_high", "weight_seed"):
            del config["graph"][key]
    run_, (g, sess) = _build(config)
    try:
        lay = config["layout"]
        csr = CSRGraph(n=g.n, m_undirected=g.nnz // 2, indptr=g.indptr,
                       indices=g.indices, weights=g.weights)
        host = build_slimsell(csr, C=lay["C"], L=lay["L"], sigma=g.n)
        want = host.to_jax()
        for field in ("cols", "row_block", "wts"):
            got, ref = getattr(sess.tiled, field), getattr(want, field)
            assert (got is None) == (ref is None)
            if ref is not None:
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(ref))
        assert (want.wts is None) == (not weighted)
        assert run_.layout["slots"] == host.cols.size
        assert run_.layout["tiles"] == host.n_tiles
        assert run_.layout["devices"] == 1
    finally:
        sess.close()


def test_layout_keys_reach_the_session(tiny_root, monkeypatch):
    """Every ``layout`` key but ``sigma`` is a keyword of the session, so a
    configuration can ask the program for a mesh."""
    import repro.serving
    from repro.core.formats import build_slimsell
    seen = {}

    class Session:
        def __init__(self, graph, **kwargs):
            seen.update(kwargs)
            self.tiled = build_slimsell(graph, C=kwargs["C"], L=kwargs["L"],
                                        sigma=graph.n).to_jax()
    monkeypatch.setattr(repro.serving, "GraphSession", Session)
    config = _config(tiny_root, mesh=[2, 2])
    _build(config)
    assert seen == {"C": 8, "L": 128, "mesh": [2, 2], "max_batch": 16}


def test_a_sigma_other_than_n_is_refused(tiny_root):
    with pytest.raises(ValueError, match="sigma"):
        _build(_config(tiny_root, sigma=1))


# ------------------------------------------------------------------ faults


def _state_unchanged(monkeypatch):
    from repro.core import engine

    def run(self, tiled, ctx, state):
        zero = jnp.asarray(0, jnp.int32)
        return state, zero, (zero, zero)
    monkeypatch.setattr(engine.FixpointHandle, "run", run)


def _half_batch_left_out(monkeypatch):
    """The last ceil(width / 2) columns of every batch keep their initial
    state."""
    from repro.core import engine
    real = engine.FixpointHandle.run

    def run(self, tiled, ctx, state):
        init = {k: jnp.array(v) for k, v in state.items()}
        out, iters, tiles = real(self, tiled, ctx, state)
        width = self.batch_width
        if width:
            keep = width // 2
            out = {k: (v.at[:, keep:].set(init[k][:, keep:])
                       if v.ndim == 2 and v.shape[1] == width else v)
                   for k, v in out.items()}
        return out, iters, tiles
    monkeypatch.setattr(engine.FixpointHandle, "run", run)


def _answer_altered(monkeypatch):
    """Every answer has one reached vertex moved where it is produced."""
    from repro.serving import dispatch
    real = dispatch.Dispatcher._finish

    def finish(self, query, **fields):
        values = fields.get("values")
        if values is not None:
            values = np.array(values)
            far = np.nonzero((values > 0) & np.isfinite(values))[0]
            if far.size:
                values[far[0]] = values[far[0]] * 2 + 1
            fields["values"] = values
        return real(self, query, **fields)
    monkeypatch.setattr(dispatch.Dispatcher, "_finish", finish)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_batch_left_out": _half_batch_left_out,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, workload,
                                          fault):
    """One chip: no exchange between chips to leave out."""
    FAULTS[fault](monkeypatch)
    result = run(tiny_root, workload)
    assert not result["correct"], result["checks"]


# -------------------------------------------------------------- no chip


def test_cpu_is_refused(tiny_root):
    with pytest.raises(harness.NoChip):
        harness.run_cell("g500-bfs", 1, SECONDS, False, root=tiny_root)


def test_too_few_chips_is_refused():
    with pytest.raises(harness.NoChip):
        harness.device_info(chips=4, require_tpu=False)


def test_unknown_device_kind_has_no_peaks():
    from bench.peaks import peaks_for
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v99")


def test_main_prints_no_result_without_a_chip(capsys):
    assert harness.main(["--workload", "g500-bfs", "--seed", "1",
                         "--seconds", "1"], 0.0) != 0
    assert capsys.readouterr().out == ""
