"""Static-analysis suite: kernel contract checker, semiring-law verifier,
AST lint golden fixtures, and the checkify sanitizer mode."""
import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from conftest import run_multidevice
from repro.analysis import contracts, laws, lint
from repro.analysis.registry import (REGISTRY, KernelCase, compact_ids_np,
                                     demo_layout)
from repro.core import debug, formats, options
from repro.core import semiring as sm
from repro.core.bfs import bfs
from repro.core.cc import cc
from repro.core.sssp import sssp

REPO = pathlib.Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "fixtures" / "lint"


def small_graph(n=64, m=300, seed=0, weights=False):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, n, m), rng.integers(0, n, m)], 1)
    w = rng.uniform(0.1, 2.0, m).astype(np.float32) if weights else None
    csr = formats.build_csr(edges, n, weights=w)
    return formats.build_slimsell(csr)


# ------------------------------------------------------- contract checker


def test_all_registered_contracts_pass():
    import repro.kernels.ops  # noqa: F401  (populates the registry)
    assert len(REGISTRY) == 7, sorted(REGISTRY)  # all pallas_call wrappers
    errors = contracts.check_all()
    assert errors == []


def test_contract_rejects_oob_index_map():
    from repro.kernels.slimsell_spmv import spmv_grid_spec
    d = demo_layout()
    T, C, L, cb = d["T"], d["C"], d["L"], d["chunk_blk"]
    out_shape = (d["n_blk"] * cb, C)
    # a corrupt row_block points one tile at chunk 99 -> output block 49,
    # far beyond the 3 existing blocks; Pallas would silently clamp
    bad_rb = d["row_block"].copy()
    bad_rb[4] = 99
    ids = np.arange(T, dtype=np.int32)
    case = KernelCase(
        name="bad/oob", grid_spec=spmv_grid_spec(T, C, L, d["x_rows"], cb,
                                                 False),
        scalar_args=(ids, bad_rb[ids], np.asarray([T], np.int32)),
        in_shapes=[(T, C, L), d["x_rows"], out_shape],
        out_shapes=[out_shape],
        chunked_out=[("out", 0)])
    errs = contracts.check_case(case)
    assert any("outside [0," in e and "clamp" in e for e in errs), errs


def test_contract_rejects_noncontiguous_revisit():
    from repro.kernels.slimsell_spmv import spmv_grid_spec
    d = demo_layout()
    T, C, L, cb = d["T"], d["C"], d["L"], d["chunk_blk"]
    out_shape = (d["n_blk"] * cb, C)
    # interleave tiles of different chunks: block order 0,1,0,... would
    # make first_visit re-init block 0 twice, dropping tile 0's partial
    ids = np.asarray([0, 3, 1, 2, 4, 5, 6, 7, 8], np.int32)
    case = KernelCase(
        name="bad/interleave",
        grid_spec=spmv_grid_spec(T, C, L, d["x_rows"], cb, False),
        scalar_args=(ids, d["row_block"][ids], np.asarray([T], np.int32)),
        in_shapes=[(T, C, L), d["x_rows"], out_shape],
        out_shapes=[out_shape],
        chunked_out=[("out", 0)])
    errs = contracts.check_case(case)
    assert any("revisited non-contiguously" in e for e in errs), errs


def test_contract_rejects_lockstep_mismatch():
    d = demo_layout()
    T, C, L, cb = d["T"], d["C"], d["L"], d["chunk_blk"]
    # a weight block pinned to tile 0 while cols follows the indirection:
    # weights would pair with the wrong columns on every tile but 0
    cols_spec = pl.BlockSpec((1, C, L), lambda t, tids, rb, na: (tids[t], 0, 0))
    pinned = pl.BlockSpec((1, C, L), lambda t, tids, rb, na: (0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(T,),
        in_specs=[cols_spec, pinned,
                  pl.BlockSpec((d["n_pad"],), lambda t, tids, rb, na: (0,))],
        out_specs=pl.BlockSpec(
            (cb, C), lambda t, tids, rb, na: (rb[tids[t]] // cb, 0)))
    case = KernelCase(
        name="bad/lockstep", grid_spec=grid_spec,
        scalar_args=(np.arange(T, dtype=np.int32), d["row_block"],
                     np.asarray([T], np.int32)),
        in_shapes=[(T, C, L), (T, C, L), (d["n_pad"],)],
        out_shapes=[(d["n_blk"] * cb, C)],
        lockstep=[(("in", 0), ("in", 1))],
        chunked_out=[("out", 0)])
    errs = contracts.check_case(case)
    assert any("lockstep" in e and "diverge" in e for e in errs), errs


def test_slimwork_compaction_contract_scenario():
    # the demo layout's slimwork scenario uses the numpy compaction twin;
    # sanity-check it matches the device implementation
    from repro.kernels.ops import compact_tile_ids
    mask = np.ones(9, bool)
    mask[[2, 6]] = False
    ids_np, na_np = compact_ids_np(mask)
    ids_dev, na_dev = compact_tile_ids(jnp.asarray(mask))
    assert np.array_equal(ids_np, np.asarray(ids_dev))
    assert np.array_equal(na_np, np.asarray(na_dev))


# ------------------------------------------------------ semiring-law verifier


def test_all_registered_semirings_satisfy_laws():
    results = laws.verify_all()
    assert set(results) == set(options.SEMIRINGS)
    for name, errs in results.items():
        assert errs == [], (name, errs)


def test_kernel_table_cross_check_passes():
    assert laws.cross_check_kernel_tables() == []


def test_broken_pseudo_semiring_rejected():
    # subtraction is neither associative nor commutative, and 0 does not
    # annihilate it — the verifier must say so
    broken = sm.Semiring(name="broken", dtype=jnp.float32, zero=0.0, one=0.0,
                         add=lambda a, b: a - b, mul=lambda a, b: a + b,
                         reduction="sum")
    errs = laws.verify_semiring(broken)
    assert any("associativity" in e for e in errs)
    assert any("commutativity" in e for e in errs)
    assert any("annihilation" in e for e in errs)


def test_unhandled_semiring_is_hard_failure(monkeypatch):
    # simulate a hand-specialized kernel table that forgot a registered
    # semiring: dispatch exhaustiveness must fail, not skip
    import repro.kernels.slimsell_spmv as spmv_mod
    real_ops = spmv_mod.semiring_ops

    def partial_table(name):
        if name == "minplus":
            raise ValueError(name)
        return real_ops(name)

    monkeypatch.setattr(spmv_mod, "semiring_ops", partial_table)
    errs = laws.cross_check_kernel_tables()
    assert any("no dispatch" in e and "minplus" in e for e in errs), errs


def test_drifted_kernel_table_is_caught(monkeypatch):
    # a kernel table whose real-semiring zero drifted from core must fail
    import repro.kernels.slimsell_spmv as spmv_mod
    real_ops = spmv_mod.semiring_ops

    def drifted(name):
        add, contrib, zero = real_ops(name)
        return (add, contrib, -1.0) if name == "real" else (add, contrib, zero)

    monkeypatch.setattr(spmv_mod, "semiring_ops", drifted)
    errs = laws.cross_check_kernel_tables()
    assert any("real" in e and "zero" in e for e in errs), errs


# ---------------------------------------------------------------- lint pass


def _findings_for(fixture, allow=frozenset()):
    return lint.lint_paths([FIXTURES / fixture], REPO, set(allow))


def test_lint_catches_traced_branch():
    rules = [f.rule for f in _findings_for("bad_traced_branch.py")]
    assert rules.count("traced-branch") == 2, rules  # and no extras
    assert set(rules) == {"traced-branch"}


def test_lint_catches_string_option():
    rules = [f.rule for f in _findings_for("bad_string_option.py")]
    assert rules == ["string-option"]


def test_lint_catches_f32_vertex_ids():
    rules = [f.rule for f in _findings_for("bad_f32_ids.py")]
    assert rules == ["f32-vertex-id", "f32-vertex-id"]


def test_lint_catches_packed_constants():
    rules = [f.rule for f in _findings_for("bad_packed_constants.py")]
    assert rules == ["packed-constants"] * 3, rules


def test_packed_constants_rule_is_allowlist_free():
    # allow entries for the rule (path-level and qualname-level) change
    # nothing: the rule's only fix is routing through core.packing
    findings = _findings_for("bad_packed_constants.py")
    keys = {k for f in findings for k in f.key_candidates()}
    assert len(_findings_for("bad_packed_constants.py", allow=keys)) == 3


def test_packing_module_is_exempt_from_packed_constants():
    packing_py = (REPO / "src" / "repro" / "core" / "packing.py")
    findings = lint.lint_paths([packing_py], REPO, set())
    assert [f for f in findings if f.rule == "packed-constants"] == []


def test_lint_catches_interpret_literal():
    rules = [f.rule for f in _findings_for("bad_interpret_literal.py")]
    assert rules == ["interpret-literal"]


def test_lint_catches_unregistered_pallas_call():
    rules = [f.rule
             for f in _findings_for("kernels/bad_unregistered_pallas.py")]
    assert rules == ["pallas-contract"]


def test_lint_allowlist_silences_by_qualname():
    [finding] = _findings_for("bad_string_option.py")
    key = f"string-option:{finding.path}::{finding.qualname}"
    assert _findings_for("bad_string_option.py", allow={key}) == []


def test_lint_clean_on_repo_sources():
    allow = lint.load_allowlist(
        REPO / "src" / "repro" / "analysis" / "lint_allow.txt")
    findings = lint.lint_paths([REPO / "src" / "repro"], REPO, allow)
    assert findings == [], [str(f) for f in findings]


# ------------------------------------------------------------- option home


def test_option_vocabularies_are_canonical():
    assert tuple(sm.SEMIRINGS) == options.SEMIRINGS
    from repro.core.spmv import BACKENDS as spmv_backends
    from repro.core.engine import DIRECTIONS as eng_directions
    from repro.core.cc import CC_SEMIRINGS as cc_semirings
    assert spmv_backends is options.BACKENDS
    assert eng_directions is options.DIRECTIONS
    assert cc_semirings is options.CC_SEMIRINGS


def test_entry_points_reject_unknown_options():
    tiled = small_graph()
    with pytest.raises((KeyError, ValueError)):
        bfs(tiled, 0, "nope")
    with pytest.raises(ValueError):
        bfs(tiled, 0, "tropical", direction="sideways")
    with pytest.raises(ValueError):
        bfs(tiled, 0, "tropical", backend="cuda")
    with pytest.raises(ValueError):
        cc(tiled, semiring="tropical")
    from repro.core import engine as eng
    from repro.core.bfs import bfs_spec
    with pytest.raises(ValueError):
        eng.run_fused(bfs_spec("tropical"), tiled, jnp.asarray(0, jnp.int32),
                      max_iters=4, direction="sideways")
    from repro.kernels import ops
    with pytest.raises(ValueError):
        ops.embedding_bag(jnp.zeros((4, 4)), jnp.zeros((8, 2), jnp.int32),
                          mode="median")


def test_interpret_default_env_override(monkeypatch):
    monkeypatch.setenv(options.INTERPRET_ENV, "1")
    assert options.default_interpret() is True
    monkeypatch.setenv(options.INTERPRET_ENV, "0")
    assert options.default_interpret() is False
    monkeypatch.setenv(options.INTERPRET_ENV, "auto")
    assert options.default_interpret() is (jax.default_backend() != "tpu")
    monkeypatch.delenv(options.INTERPRET_ENV)
    assert options.resolve_interpret(None) == options.default_interpret()
    assert options.resolve_interpret(False) is False
    monkeypatch.setenv(options.INTERPRET_ENV, "sometimes")
    with pytest.raises(ValueError):
        options.default_interpret()



def test_interpret_forced_on_tpu_raises(monkeypatch):
    # on a TPU the kernels run compiled or not at all: every way of forcing
    # interpret mode fails loudly instead of running slowly
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv(options.INTERPRET_ENV, raising=False)
    assert options.default_interpret() is False
    with pytest.raises(RuntimeError, match="interpret mode"):
        options.resolve_interpret(True)
    monkeypatch.setenv(options.INTERPRET_ENV, "1")
    with pytest.raises(RuntimeError, match=options.INTERPRET_ENV):
        options.default_interpret()
    monkeypatch.delenv(options.INTERPRET_ENV)
    with options.interpret_override(True):
        with pytest.raises(RuntimeError, match="interpret mode"):
            options.default_interpret()

# ------------------------------------------------------------ sanitizer mode


def test_sanitized_runs_match_unsanitized():
    tiled = small_graph()
    ref = bfs(tiled, 0, "tropical")
    # prior state, not "off": CI runs this file under REPRO_SANITIZE=1
    was_enabled = debug.enabled()
    for backend in ("jnp", "pallas"):
        for mode in ("fused", "hostloop"):
            with debug.checked():
                res = bfs(tiled, 0, "tropical", mode=mode, backend=backend)
            assert np.array_equal(res.distances, ref.distances), (backend,
                                                                  mode)
    assert debug.enabled() == was_enabled  # context manager restored state


def test_sanitizer_catches_oob_cols_fused_and_hostloop():
    tiled = small_graph()
    bad_cols = np.asarray(tiled.cols).copy()
    flat = bad_cols.reshape(-1)
    flat[np.nonzero(flat >= 0)[0][0]] = tiled.n + 7   # one corrupt vertex id
    bad = dataclasses.replace(tiled, cols=jnp.asarray(bad_cols))
    with debug.checked():
        with pytest.raises(Exception, match="out-of-bounds vertex ids"):
            bfs(bad, 0, "tropical", mode="fused")
        with pytest.raises(debug.SanitizerError,
                           match="out-of-bounds vertex ids"):
            bfs(bad, 0, "tropical", mode="hostloop")
    # without the sanitizer the same corrupt layout runs silently — that
    # is exactly the failure mode checked() exists for (suspended() forces
    # it off even when CI set REPRO_SANITIZE=1 for the whole process)
    with debug.suspended():
        res = bfs(bad, 0, "tropical", mode="fused")
    assert res.iterations >= 0


def test_sanitizer_catches_nan_weights():
    tiled = small_graph(weights=True)
    w = np.asarray(tiled.wts).copy()
    live = np.nonzero(np.asarray(tiled.cols).reshape(-1) >= 0)[0]
    w.reshape(-1)[live[0]] = np.nan
    bad = dataclasses.replace(tiled, wts=jnp.asarray(w))
    with pytest.raises(debug.SanitizerError, match="NaN/inf/negative"):
        debug.validate_layout_host(bad)
    with debug.checked():
        with pytest.raises(Exception, match="NaN|poison infinity"):
            # explicit delta: the default derives the bucket width from the
            # (poisoned) mean weight and would fail before the engine runs
            sssp(bad, 0, mode="fused", delta=1.0)


def test_sanitizer_sssp_and_cc_clean():
    tiled = small_graph(weights=True)
    ref = sssp(tiled, 0)
    with debug.checked():
        res = sssp(tiled, 0)
        labels = cc(tiled).labels
    assert np.allclose(res.distances, ref.distances, equal_nan=True)
    assert np.array_equal(labels, cc(tiled).labels)


def test_check_gather_catches_seeded_oob():
    def gather(table, idx):
        debug.check_gather(idx, table.shape[0])
        return jnp.take(table, idx, axis=0)

    table = jnp.arange(8.0)
    good = jnp.asarray([0, 3, 7])
    bad_idx = jnp.asarray([0, 3, 11])
    with debug.checked():
        out = debug.call_checked(gather, table, good)
        assert np.array_equal(np.asarray(out), [0.0, 3.0, 7.0])
        with pytest.raises(Exception, match="gather index out of bounds"):
            debug.call_checked(gather, table, bad_idx)
    # unsanitized jnp.take never raises on OOB — it clips or NaN-fills
    # depending on mode/tracing, which is the motivating silent hazard
    last = float(jnp.take(table, bad_idx, axis=0)[-1])
    assert last == 7.0 or np.isnan(last)


def test_sanitizer_enable_disable_and_suspend():
    with debug.suspended():   # a REPRO_SANITIZE=1 process starts enabled
        assert not debug.enabled()
        debug.enable()
        try:
            assert debug.enabled()
            assert debug.errors() is not None
            with debug.suspended():
                assert not debug.enabled()
            assert debug.enabled()  # suspension restored the enabled state
        finally:
            debug.disable()
        assert not debug.enabled()


def test_sanitized_distributed_bfs():
    run_multidevice("""
import numpy as np
from repro.core.dist_bfs import make_mesh
from repro.core import debug
from repro.core.dist_bfs import partition_slimsell, make_dist_bfs
from repro.graphs.generators import kronecker
csr = kronecker(7, 8, seed=3)
root = int(np.argmax(csr.deg))
mesh = make_mesh((2, 2), ("data", "model"))
dist = partition_slimsell(csr, R=2, Co=2, C=8, L=16)
fn = make_dist_bfs(mesh, dist, "tropical", max_iters=64)
d0, _ = fn(dist.cols, dist.row_block, dist.row_vertex, np.int32(root))
with debug.checked():
    d1, _ = fn(dist.cols, dist.row_block, dist.row_vertex, np.int32(root))
assert np.array_equal(np.asarray(d0), np.asarray(d1))
print("PASS")
""", n_devices=4)
