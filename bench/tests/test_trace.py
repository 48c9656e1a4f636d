"""The trace reduction on synthetic events."""
import pytest

from bench import harness, trace


def test_union_and_gaps():
    u = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert trace.gaps(u, 0, 10) == [(3, 5), (8, 10)]
    assert trace.clip(u, 1, 6) == [(1, 3), (5, 6)]


def test_reduce_events_busy_ops_and_gaps():
    ops = {"/device:TPU:0": [("fusion.1", 100, 400), ("fusion.2", 300, 500),
                             ("gather", 700, 800), ("fusion.1", 900, 1300)]}
    spans = {"bench.window": [(0, 1000)],
             "bench.sleep": [(0, 90)],
             "bench.wait": [(500, 700), (800, 1000)],
             "other": [(0, 1000)]}
    out = trace.reduce_events(ops, spans)
    assert out["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 500] + [700, 800] + [900, 1000] inside the window
    assert out["busy_s"] == pytest.approx(600e-9)
    names = [n for n, _ in out["device_ops"]]
    assert names[0] == "fusion.1"
    assert dict(out["device_ops"])["fusion.1"] == pytest.approx(400e-9)
    gaps = out["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([200e-9, 100e-9, 100e-9])
    assert gaps[0][0] == "wait"
    assert sorted(g[0] for g in gaps[1:]) == ["sleep", "wait"]


def test_programs_split_busy_time_and_parent_transform():
    ops = {"/device:TPU:0": [("jit_impl/%while.1 while", 0, 300),
                             ("jit_impl/%fusion.2 fusion", 10, 200),
                             ("jit_dp_transform/%fusion.9 fusion", 400, 900),
                             ("loose", 950, 1000)]}
    out = trace.reduce_events(ops, {"bench.window": [(0, 1000)]})
    assert out["programs"] == pytest.approx(
        {"jit_impl": 300e-9, "jit_dp_transform": 500e-9, "": 50e-9})
    assert trace.parent_transform_s(out["programs"]) == pytest.approx(500e-9)
    assert trace.parent_transform_s({"jit_impl": 1.0}) == 0.0
    assert trace.parent_transform_s({"jit_scan": 1.0}) == 0.0


def _batches_run(device_trace, completed, sweeps):
    return harness.Run(workload="g500-bfs", kind="batches", seed=1,
                       seconds=1.0, cell={}, config={}, traffic={},
                       window={"completed": completed},
                       counters={"sweeps_total": sweeps},
                       device_trace=device_trace)


def test_transform_is_read_per_key_and_left_out_of_the_sweep():
    """The batched parent transform's program counts for
    ``parent_ms_per_key.g500`` and not for ``dev_ms_per_sweep.g500``."""
    ops = {"/device:TPU:0": [
        ("jit_fixpoint_multi_bfs_tropical/%fusion.39 fusion", 0, 6e8),
        ("jit_dp_transform/%fusion.36 fusion", 6e8, 8e8),
        ("jit_fixpoint_multi_bfs_tropical/%fusion.39 fusion", 8e8, 14e8)]}
    t = trace.reduce_events(ops, {"bench.window": [(0, 15e8)]})
    run = _batches_run(t, completed=4, sweeps=3)
    parent = harness.load_reader("parent_ms_per_key.g500")(run)
    sweep = harness.load_reader("dev_ms_per_sweep.g500")(run)
    assert parent == pytest.approx(200.0 / 4)
    assert sweep == pytest.approx(1200.0 / 3)


def test_transform_reader_is_silent_without_the_transform():
    ops = {"/device:TPU:0": [("jit_fixpoint_multi_sssp/%f fusion", 0, 9e8)]}
    t = trace.reduce_events(ops, {"bench.window": [(0, 1e9)]})
    run = _batches_run(t, completed=16, sweeps=3)
    assert harness.load_reader("parent_ms_per_key.g500")(run) is None
    assert harness.load_reader("dev_ms_per_sweep.g500")(run) == \
        pytest.approx(300.0)


def test_busy_is_averaged_over_devices():
    ops = {"/device:TPU:0": [("a", 0, 100)], "/device:TPU:1": [("a", 0, 50)]}
    out = trace.reduce_events(ops, {"bench.window": [(0, 100)]})
    assert out["busy_s"] == pytest.approx(75e-9)


def test_gap_with_no_span_is_none():
    ops = {"/device:TPU:0": [("a", 0, 10)]}
    out = trace.reduce_events(ops, {"bench.window": [(0, 100)]})
    assert out["idle_gaps"] == [["none", pytest.approx(90e-9)]]


def test_missing_window_or_device_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_events({"/device:TPU:0": [("a", 0, 1)]}, {})
    with pytest.raises(ValueError):
        trace.reduce_events({}, {"bench.window": [(0, 1)]})


def test_self_time_subtracts_nested_ops():
    ops = [("while", 0, 100), ("body_a", 10, 40), ("body_b", 50, 90),
           ("inner", 60, 70), ("after", 100, 120)]
    st = trace.self_times(ops)
    assert st["while"] == 100 - 30 - 40
    assert st["body_b"] == 40 - 10
    assert st["inner"] == 10 and st["after"] == 20


def test_short_name_keeps_instruction_and_opcode():
    hlo = ("%fusion.75 = f32[524288]{0:T(1024)S(1)} fusion(f32[1048576]"
           "{0:T(1024)S(1)} %bitcast.73), kind=kCustom")
    assert trace.short_name(hlo, "jit_impl(123)") == \
        "jit_impl/%fusion.75 fusion"
    loop = ("%while.27 = (s32[8,1]{0,1:T(1,128)}, s32[]{:T(128)}) "
            "while((s32[8,1]{0,1:T(1,128)}, s32[]{:T(128)}) %tuple.53)")
    assert trace.short_name(loop) == "%while.27 while"
