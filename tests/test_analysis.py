"""Static verifier + repo lint tests (``repro.analysis``).

Golden known-bad fixtures for every rule family — an over-budget row
panel at a wide RHS, a misaligned col tile, a mutated kernel copy whose
DMA wait is gone, a bare-assert snippet — plus the clean-tree acceptance
check (the real repo must produce zero findings) and the autotune
prefilter contract (infeasible candidates are recorded and never
measured).
"""
import dataclasses
import os
import textwrap

import numpy as np
import pytest

from repro.analysis import (KernelConfigError, kernel_check, lint, vmem)
from repro.analysis.__main__ import main as analysis_main, run as analysis_run
from repro.core.incrs import InCRS
from repro.kernels import autotune, ops
from repro.kernels.incrs_spmm import _resolve_row_tile
from repro.sparse import SparseSpec
from repro.sparse import api

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A config whose reuse/pipelined row panel (bm x Np f32) is 4 MiB — over
# the 2 MiB panel working-set budget — used as the canonical over-budget
# fixture throughout.
WIDE = dict(m=128, n=8192, bm=128, bn=128, n_sections=4, smax=64,
            section=256)
SMALL = dict(m=128, n=1024, bm=128, bn=128, n_sections=4, smax=64,
             section=256)


def _kernel_src():
    with open(kernel_check.kernel_source_path()) as f:
        return f.read()


def _rules(violations):
    return {v.rule for v in violations}


# ----------------------------------------------------------------------
# VMEM footprint model.
def test_footprint_terms_sum_to_total():
    for variant in vmem.INCRS_VARIANTS:
        fp = vmem.incrs_footprint(variant, **SMALL)
        assert fp.total_bytes == sum(t.nbytes for t in fp.terms)
        assert fp.total_bytes > 0
        assert fp.largest.nbytes == max(t.nbytes for t in fp.terms)


def test_footprint_row_panel_matches_hand_formula():
    # reuse holds a (bm, Np) f32 panel in scratch: 128 * 8192 * 4 B.
    fp = vmem.incrs_footprint("reuse", **WIDE)
    panel = fp.term("row_panel_accumulator")
    assert panel.single_bytes == 128 * 8192 * 4 == 4 * 1024 * 1024
    # pipelined double-buffers a (2, section, bn) RHS stream window.
    fp = vmem.incrs_footprint("pipelined", **WIDE)
    stream = fp.term("rhs_stream_window")
    assert stream.nbytes == 2 * WIDE["section"] * WIDE["bn"] * 4


def test_resolve_row_tile_mirrors_kernel():
    for m, bm in [(127, 128), (32, 128), (4, 128), (1000, 128),
                  (17, 128), (128, 32)]:
        assert vmem.resolve_row_tile(m, bm) == _resolve_row_tile(m, bm)


def test_vmem_budget_env_override(monkeypatch):
    assert vmem.vmem_budget() == vmem.DEFAULT_VMEM_BUDGET
    monkeypatch.setenv(vmem.VMEM_BUDGET_ENV, str(1 << 20))
    assert vmem.vmem_budget() == 1 << 20
    assert vmem.vmem_budget(123) == 123          # explicit arg wins


# ----------------------------------------------------------------------
# Config feasibility checker.
def test_clean_config_has_no_violations():
    for variant in vmem.INCRS_VARIANTS:
        assert kernel_check.check_incrs_config(variant, **SMALL) == []


def test_over_budget_panel_at_wide_rhs():
    vs = kernel_check.check_incrs_config("reuse", **WIDE)
    assert _rules(vs) == {kernel_check.RULE_PANEL}
    v = vs[0]
    assert v.term == "row_panel_accumulator"
    assert v.nbytes == 4 * 1024 * 1024 and v.limit == vmem.PANEL_BYTES
    # The grid-ordered baseline re-expands per col tile but holds no
    # panel — it stays feasible at the same shape.
    assert kernel_check.check_incrs_config("expand", **WIDE) == []


def test_misaligned_bn_flagged():
    cfg = dict(SMALL, bn=100)
    vs = kernel_check.check_incrs_config("expand", **cfg)
    assert _rules(vs) == {kernel_check.RULE_ALIGN}
    # wider than the lane-padded operand is also an alignment violation
    cfg = dict(SMALL, n=128, bn=512)
    vs = kernel_check.check_incrs_config("expand", **cfg)
    assert kernel_check.RULE_ALIGN in _rules(vs)


def test_grid_bounds_rules():
    vs = kernel_check.check_incrs_config(
        "expand", **dict(SMALL, smax=512))      # smax > section
    assert _rules(vs) == {kernel_check.RULE_GRID}
    vs = kernel_check.check_incrs_config(
        "expand", k=999, **SMALL)               # k != n_sections * section
    assert _rules(vs) == {kernel_check.RULE_GRID}


def test_hard_budget_violation_names_largest_term():
    vs = kernel_check.check_incrs_config("expand", budget=64 * 1024,
                                         **SMALL)
    assert _rules(vs) == {kernel_check.RULE_VMEM}
    fp = vmem.incrs_footprint("expand", **SMALL)
    assert vs[0].term == fp.largest.name
    assert vs[0].nbytes == fp.total_bytes


def test_require_feasible_raises_structured_error():
    with pytest.raises(KernelConfigError) as ei:
        kernel_check.require_feasible("reuse", context="unit-test", **WIDE)
    err = ei.value
    assert isinstance(err, ValueError)           # callers catch ValueError
    assert err.violations[0].term == "row_panel_accumulator"
    assert "unit-test" in str(err)
    assert "row_panel_accumulator" in str(err)


def test_rules_subset_restricts_families():
    # Budget-only check must NOT fire alignment on a misaligned bn.
    cfg = dict(SMALL, bn=100)
    vs = kernel_check.check_incrs_config(
        "expand", rules=kernel_check.BUDGET_RULES, **cfg)
    assert vs == []


# ----------------------------------------------------------------------
# DMA pairing of the double-buffered kernel.
def test_real_kernel_dma_protocol_is_sound():
    assert kernel_check.check_dma_pairing() == []


def test_real_kernel_scratch_matches_model():
    assert kernel_check.check_scratch_drift() == []
    assert kernel_check.check_kernel_invariants() == []


WAIT_LINE = "        block_copy(t % 2, t).wait()\n"


def test_mutated_kernel_missing_wait_is_caught():
    src = _kernel_src()
    assert WAIT_LINE in src
    findings = kernel_check.check_dma_pairing(src.replace(WAIT_LINE, ""))
    rules = {f.rule for f in findings}
    # No wait -> the dot reads a slot still in flight, the prefetch
    # re-starts an in-flight slot, and copies leak past loop exit.
    assert kernel_check.RULE_DMA_READ in rules
    assert kernel_check.RULE_DMA_DOUBLE in rules or \
        kernel_check.RULE_DMA_LEAK in rules


def test_mutated_kernel_wrong_wait_slot_is_caught():
    src = _kernel_src()
    mutated = src.replace(WAIT_LINE,
                          "        block_copy((t + 1) % 2, t).wait()\n")
    findings = kernel_check.check_dma_pairing(mutated)
    assert findings, "waiting the wrong buffer slot must not verify"
    assert kernel_check.RULE_DMA_READ in {f.rule for f in findings}


def test_mutated_kernel_double_start_is_caught():
    src = _kernel_src()
    start = "            block_copy((t + 1) % 2, t + 1).start()\n"
    assert start in src
    mutated = src.replace(
        start, "            block_copy(t % 2, t + 1).start()\n")
    findings = kernel_check.check_dma_pairing(mutated)
    assert kernel_check.RULE_DMA_DOUBLE in {f.rule for f in findings}


def test_mutated_scratch_signature_is_drift():
    src = _kernel_src()
    entry = "pltpu.VMEM((bm, section), jnp.float32)]"
    assert src.count(entry) >= 1
    mutated = src.replace(entry, "]", 1)   # drop a scratch buffer
    findings = kernel_check.check_scratch_drift(mutated)
    assert kernel_check.RULE_DRIFT in {f.rule for f in findings}


# ----------------------------------------------------------------------
# Repo lint rules (golden snippets).
def _lint(snippet, rules=None):
    return lint.lint_source(textwrap.dedent(snippet), "x.py", rules=rules)


def test_bare_assert_flagged_and_tag_exempts():
    bad = _lint("""
        def f(x):
            assert x > 0, "x must be positive"
    """)
    assert [f.rule for f in bad] == [lint.RULE_ASSERT]
    assert bad[0].line == 3
    ok_same = _lint("""
        def f(x):
            assert x > 0  # lint: allow-assert
    """)
    ok_above = _lint("""
        def f(x):
            # internal invariant  # lint: allow-assert
            assert x > 0
    """)
    assert ok_same == [] and ok_above == []


def test_validation_survives_o_rule():
    gated = _lint("""
        def f(x):
            if __debug__:
                if x < 0:
                    raise ValueError("negative")
    """, rules=(lint.RULE_SURVIVES_O,))
    assert [f.rule for f in gated] == [lint.RULE_SURVIVES_O]
    msg = _lint("""
        def f(x):
            assert x > 0, ValueError("x must be positive")
    """, rules=(lint.RULE_SURVIVES_O,))
    assert [f.rule for f in msg] == [lint.RULE_SURVIVES_O]
    clean = _lint("""
        def f(x):
            if x < 0:
                raise ValueError("negative")
    """, rules=(lint.RULE_SURVIVES_O,))
    assert clean == []


_PYTREE_SNIPPET = """
    import dataclasses
    import jax

    @dataclasses.dataclass{meta_args}
    class Meta:
        section: int
        idx: "np.ndarray"{idx_field}

    @dataclasses.dataclass
    class Params:
        values: object
        meta: Meta

    jax.tree_util.register_pytree_node(Params, _fl, _un)
"""


def test_pytree_meta_default_dataclass_flagged():
    bad = _lint(_PYTREE_SNIPPET.format(meta_args="", idx_field=""),
                rules=(lint.RULE_META,))
    assert [f.rule for f in bad] == [lint.RULE_META]
    assert "Meta" in bad[0].message


def test_pytree_meta_eq_false_is_clean():
    ok = _lint(_PYTREE_SNIPPET.format(meta_args="(eq=False)",
                                      idx_field=""),
               rules=(lint.RULE_META,))
    assert ok == []


def test_pytree_meta_frozen_needs_compare_false_on_arrays():
    bad = _lint(_PYTREE_SNIPPET.format(meta_args="(frozen=True)",
                                       idx_field=""),
                rules=(lint.RULE_META,))
    assert [f.rule for f in bad] == [lint.RULE_META]
    assert "idx" in bad[0].message
    ok = _lint(_PYTREE_SNIPPET.format(
        meta_args="(frozen=True)",
        idx_field=" = dataclasses.field(compare=False)"),
        rules=(lint.RULE_META,))
    assert ok == []


def test_legacy_names_rule():
    bad = _lint("""
        from repro.kernels.ops import bsr_matmul
        y = incrs_linear_apply(p, x)
        z = ops.incrs_spmm(i, v, b)
    """, rules=(lint.RULE_LEGACY,))
    assert len(bad) == 3
    assert all(f.rule == lint.RULE_LEGACY for f in bad)
    ok = _lint("""
        bsr_matmul = shim          # defining the shim (Store ctx) is fine
        y = incrs_spmm(i, v, b)    # live kernel entry, not the ops shim
    """, rules=(lint.RULE_LEGACY,))
    assert ok == []


def test_finding_format_is_file_line_rule_message():
    f = lint.Finding("src/repro/x.py", 12, "no-bare-assert", "msg")
    assert f.format() == "src/repro/x.py:12 no-bare-assert msg"


# ----------------------------------------------------------------------
# Clean-tree acceptance: the real repo produces zero findings.
def test_repo_tree_is_clean():
    findings = analysis_run(REPO)
    assert findings == [], "\n".join(f.format() for f in findings)


def test_cli_check_exits_zero_on_clean_tree(capsys):
    assert analysis_main(["--check", "--root", REPO]) == 0
    assert analysis_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert lint.RULE_ASSERT in out


# ----------------------------------------------------------------------
# Autotune prefilter: infeasible candidates are recorded, never measured.
def _own_cache(monkeypatch, tmp_path):
    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path / "cache.json"))
    autotune.clear_memory_cache()


def test_split_candidates_skips_wide_panels():
    feasible, skipped = autotune.split_candidates(
        WIDE["m"], WIDE["n"], section=WIDE["section"],
        n_sections=WIDE["n_sections"], smax=WIDE["smax"])
    assert feasible and skipped
    assert all(s["variant"] in ("reuse", "pipelined") for s in skipped)
    assert all(s["rule"] in kernel_check.LAUNCH_RULES for s in skipped)
    assert all(s["bytes"] > s["limit"] for s in skipped)
    skipped_keys = {(s["variant"], s["bm"], s["bn"]) for s in skipped}
    assert skipped_keys.isdisjoint(set(feasible))
    # Every candidate is accounted for: feasible + skipped = the space.
    assert len(feasible) + len(skipped) == \
        len(autotune.candidate_space(WIDE["m"], WIDE["n"]))


def test_tune_skips_infeasible_and_never_measures_them(
        rng, monkeypatch, tmp_path):
    _own_cache(monkeypatch, tmp_path)
    a = np.where(rng.random((32, 64)) < 0.2,
                 rng.normal(size=(32, 64)), 0.0).astype(np.float32)
    inc = InCRS.from_dense(a, section=32)
    prep = ops.prepare_incrs(inc)
    b = rng.normal(size=(64, 128)).astype(np.float32)
    # Budget = the smallest candidate footprint: everything bigger is
    # provably infeasible, at least the minimal config survives.
    m = prep.padded_rows
    totals = {
        (v, bm, bn): vmem.incrs_footprint(
            v, m=m, n=128, bm=bm, bn=bn,
            n_sections=prep.n_sections, smax=prep.idx.shape[2],
            section=prep.section).total_bytes
        for v, bm, bn in autotune.candidate_space(m, 128)}
    budget = min(totals.values())
    cfg = autotune.tune(prep.idx, prep.val, b, section=prep.section,
                        interpret=True, reps=1, persist=False,
                        vmem_budget=budget)
    sweep = autotune.LAST_SWEEP
    assert not sweep.cache_hit
    assert sweep.skipped_infeasible, "tiny budget must skip candidates"
    skipped = {(s["variant"], s["bm"], s["bn"])
               for s in sweep.skipped_infeasible}
    measured = {(r["variant"], r["bm"], r["bn"]) for r in sweep.measured}
    assert measured and measured.isdisjoint(skipped)
    assert (cfg.variant, cfg.bm, cfg.bn) in measured
    assert totals[(cfg.variant, cfg.bm, cfg.bn)] <= budget
    assert sweep.winner == cfg
    assert sweep.n_candidates == len(skipped) + len(
        autotune.split_candidates(m, 128, section=prep.section,
                                  n_sections=prep.n_sections,
                                  smax=prep.idx.shape[2],
                                  vmem_budget=budget)[0])


def test_tune_with_no_feasible_candidate_raises(rng, monkeypatch,
                                                tmp_path):
    _own_cache(monkeypatch, tmp_path)
    a = np.where(rng.random((32, 64)) < 0.2,
                 rng.normal(size=(32, 64)), 0.0).astype(np.float32)
    inc = InCRS.from_dense(a, section=32)
    prep = ops.prepare_incrs(inc)
    b = rng.normal(size=(64, 128)).astype(np.float32)
    with pytest.raises(KernelConfigError) as ei:
        autotune.tune(prep.idx, prep.val, b, section=prep.section,
                      interpret=True, reps=1, persist=False,
                      vmem_budget=1)
    assert ei.value.violations[0].rule == kernel_check.RULE_VMEM


# ----------------------------------------------------------------------
# Plan/serve surfaces reject provably infeasible configs.
def _incrs_plan(rng, n_cols, tune="off", mask=None):
    if mask is None:
        mask = (rng.random((256, 128)) < 0.1)    # W (d_in, d_out)
    spec = SparseSpec("incrs", mask=mask)
    return api.plan(spec, rhs_shape=(256, n_cols), tune=tune)


def test_plan_raises_on_infeasible_cached_config(rng, monkeypatch,
                                                 tmp_path):
    _own_cache(monkeypatch, tmp_path)
    mask = (rng.random((256, 128)) < 0.1)
    p0 = _incrs_plan(rng, 8192, tune="off", mask=mask)
    idx, section = p0._tuning_arrays()
    key = autotune.cache_key(idx.shape[1], idx.shape[0], idx.shape[2],
                             section, 8192,
                             autotune.backend_name(ops.INTERPRET))
    # A poisoned cache entry: reuse at bm=128 holds a 4 MiB row panel at
    # 8192 cols — over the panel budget. plan() must refuse to attach it.
    autotune._MEM[key] = autotune.TunedConfig("reuse", 128, 128, 1.0, 1.0)
    with pytest.raises(KernelConfigError) as ei:
        _incrs_plan(rng, 8192, tune="cache", mask=mask)
    assert ei.value.violations[0].term == "row_panel_accumulator"
    # The same spec plans fine at a narrow RHS (no cache entry there).
    assert _incrs_plan(rng, 128, tune="cache", mask=mask).tuned is None


def test_plan_check_feasible_noop_for_untuned(rng):
    p0 = _incrs_plan(rng, 8192, tune="off")
    p0.check_feasible(8192)                      # untuned: no-op


def test_engine_rejects_infeasible_bound_plan(rng, monkeypatch, tmp_path):
    from repro.serve.engine import SpMMEngine
    _own_cache(monkeypatch, tmp_path)
    p0 = _incrs_plan(rng, 8192, tune="off")
    bad = dataclasses.replace(
        p0, tuned=autotune.TunedConfig("reuse", 128, 128, 1.0, 1.0))
    bound = bad.bind(bad.pack(np.zeros((256, 128), np.float32)))
    with pytest.raises(KernelConfigError):
        SpMMEngine(bound, max_wave_cols=8192, interpret=True)
    # The identical plan serves fine at a feasible wave width.
    eng = SpMMEngine(bound, max_wave_cols=256, interpret=True)
    assert eng is not None


# ----------------------------------------------------------------------
# PR 8: rule registry, --json mode, pattern-driven DMA, multi-module
# drift, and the grid-interpreter bounds prefilter.
from repro.analysis import grid_interp, registry  # noqa: E402


def test_registry_merges_every_rule_family():
    rules = registry.all_rules()
    assert set(lint.ALL_RULES) <= set(rules)
    assert set(kernel_check.RULES) <= set(rules)
    assert set(grid_interp.RULES) <= set(rules)
    # Every pass-declared rule has a description (no silent omissions).
    for p in registry.PASSES:
        for r in p.rules:
            assert r in rules, f"pass {p.name} rule {r} undescribed"
    assert all(isinstance(d, str) and d for d in rules.values())


def test_list_rules_includes_formerly_omitted_dma_rules(capsys):
    # PR 7's CLI hand-enumerated kernel rules and dropped these two.
    assert analysis_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in (kernel_check.RULE_DMA_DOUBLE,
                 kernel_check.RULE_DMA_OPAQUE, grid_interp.RULE_OOB,
                 grid_interp.RULE_RACE, grid_interp.RULE_COVERAGE):
        assert rule in out, f"--list-rules omits {rule}"


def test_cli_prints_proof_matrix(capsys):
    assert analysis_main(["--root", REPO]) == 0
    out = capsys.readouterr().out
    assert "bounds" in out and "accumulator" in out
    assert "incrs_spmm_pipelined" in out


def test_json_report_structure(tmp_path, capsys):
    import json
    report_path = tmp_path / "report.json"
    assert analysis_main(["--check", "--root", REPO,
                          "--json", str(report_path)]) == 0
    report = json.loads(report_path.read_text())
    assert report["count"] == 0 and report["findings"] == []
    assert set(registry.all_rules()) == set(report["rules"])
    assert set(report["proof_matrix"]) == set(grid_interp.KERNELS)
    for row in report["proof_matrix"].values():
        assert set(row) == set(grid_interp.PROPERTIES)
    assert {p["name"] for p in report["passes"]} == \
        {p.name for p in registry.PASSES}


# Pattern-driven DMA pairing: discovery + a helper-free kernel.
def test_dma_discovery_finds_the_pipelined_kernel():
    src = _kernel_src()
    assert kernel_check.discover_dma_kernels(src) == ["_kernel_pipelined"]
    auto = kernel_check.check_dma_pairing_auto()
    assert auto == [], [f.format() for _, f in auto]


def test_dma_auto_catches_mutation_in_any_module():
    mutated = _kernel_src().replace(WAIT_LINE, "")
    findings = kernel_check.check_dma_pairing_auto(
        {"incrs_spmm.py": mutated})
    assert findings
    assert all(module == "incrs_spmm.py" for module, _ in findings)
    assert kernel_check.RULE_DMA_READ in {f.rule for _, f in findings}


_INLINE_DMA = """
def _kernel_merge(src_hbm, o_ref, buf, sem):
    pltpu.make_async_copy(src_hbm.at[0], buf.at[0], sem.at[0]).start()
    pltpu.make_async_copy(src_hbm.at[0], buf.at[0], sem.at[0]).wait()
    o_ref[...] = buf[0]
"""


def test_inline_straight_line_dma_kernel_is_verified():
    # No local copy helper, no fori_loop: the generalized checker still
    # proves the protocol (the coming SpGEMM merge-kernel shape).
    assert kernel_check.discover_dma_kernels(_INLINE_DMA) == \
        ["_kernel_merge"]
    assert kernel_check.check_dma_pairing(_INLINE_DMA,
                                          func="_kernel_merge") == []
    broken = _INLINE_DMA.replace(
        "    pltpu.make_async_copy(src_hbm.at[0], buf.at[0], "
        "sem.at[0]).wait()\n", "")
    findings = kernel_check.check_dma_pairing(broken,
                                              func="_kernel_merge")
    rules = {f.rule for f in findings}
    assert kernel_check.RULE_DMA_READ in rules
    assert kernel_check.RULE_DMA_LEAK in rules


# Multi-module scratch drift (flash attention now modelled).
def test_expected_scratch_covers_every_kernel():
    assert set(vmem.EXPECTED_SCRATCH) == set(grid_interp.KERNELS)


def test_flash_scratch_drift_is_caught():
    path = os.path.join(os.path.dirname(
        kernel_check.kernel_source_path()), "flash_attention.py")
    with open(path) as f:
        src = f.read()
    anchor = "pltpu.VMEM((bq, 1), jnp.float32),     # running max m\n"
    assert anchor in src
    findings = kernel_check.check_scratch_drift(
        sources={"flash_attention.py": src.replace(anchor, "")})
    assert kernel_check.RULE_DRIFT in {f.rule for f in findings}
    assert any("flash_attention" in f.message for f in findings)


def test_flash_footprint_fits_budget_at_default_tiles():
    fp = vmem.flash_footprint(lanes=32, sq=2048, sk=2048, hd=128)
    assert fp.total_bytes == sum(t.nbytes for t in fp.terms)
    assert fp.total_bytes < vmem.DEFAULT_VMEM_BUDGET
    # Scratch terms mirror the kernel's three VMEM buffers.
    scratch = [t for t in fp.terms if t.where == "scratch"]
    assert len(scratch) == len(vmem.EXPECTED_SCRATCH["flash_attention"])


# Autotune + plan() reject bounds-infeasible candidates statically.
def _oob_incrs_source():
    anchor = "sl = pl.dslice(j * bn, bn)"
    src = _kernel_src()
    assert anchor in src
    return src.replace(anchor, "sl = pl.dslice(j * bn + 1, bn)", 1)


def test_split_candidates_skips_bounds_infeasible(monkeypatch):
    oob = _oob_incrs_source()
    monkeypatch.setattr(grid_interp, "_load_source",
                        lambda module, sources=None: oob)
    monkeypatch.setattr(grid_interp, "_BOUNDS_CACHE", {})
    feasible, skipped = autotune.split_candidates(
        1024, 4096, section=256, n_sections=16, smax=64)
    oob_skips = [s for s in skipped
                 if s["rule"] == grid_interp.RULE_OOB]
    assert oob_skips, "seeded OOB kernel must be recorded as skipped"
    # The mutation is in the reuse kernel body: every reuse candidate is
    # rejected before measurement, the other variants are unaffected.
    assert all(s["variant"] == "reuse" for s in oob_skips)
    assert all(v != "reuse" for v, _, _ in feasible)
    assert {(s["variant"], s["bm"], s["bn"])
            for s in skipped}.isdisjoint(set(feasible))


def test_plan_rejects_bounds_infeasible_cached_config(
        rng, monkeypatch, tmp_path):
    _own_cache(monkeypatch, tmp_path)
    oob = _oob_incrs_source()
    mask = (rng.random((256, 128)) < 0.1)
    p0 = _incrs_plan(rng, 128, tune="off", mask=mask)
    idx, section = p0._tuning_arrays()
    key = autotune.cache_key(idx.shape[1], idx.shape[0], idx.shape[2],
                             section, 128,
                             autotune.backend_name(ops.INTERPRET))
    autotune._MEM[key] = autotune.TunedConfig("reuse", 128, 128, 1.0, 1.0)
    monkeypatch.setattr(grid_interp, "_load_source",
                        lambda module, sources=None: oob)
    monkeypatch.setattr(grid_interp, "_BOUNDS_CACHE", {})
    with pytest.raises(KernelConfigError) as ei:
        _incrs_plan(rng, 128, tune="cache", mask=mask)
    assert ei.value.violations[0].rule == grid_interp.RULE_OOB
