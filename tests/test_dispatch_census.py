"""Dispatch-count pins via the semantic auditor's registry.

The whole fused-fit design exists so one GAME fit is TWO dispatches
(slab materialization + the whole-fit program; a warm start adds one
sibling executable). These tests pin those counts through the auditor's
own contract builders, so a future change that accidentally splits a
program — a host sync in the middle of the fit, a λ baked static, an
operand promoted to a static — fails loudly here, not silently on the
TPU bill.

Also the first coverage for utils/compile_cache.cache_stats (the
hit/miss counters ``benchmark/sut.py`` reads for ``compile.events.refit``
and ``compile.cache_loads.retrain``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from photon_tpu.analysis import program


@pytest.fixture(scope="module")
def fused_trace():
    with jax.enable_x64(False):
        return program.build_fused_fit()


@pytest.fixture(scope="module")
def unfused_trace():
    with jax.enable_x64(False):
        return program.build_unfused_update()


def _all_signatures(trace, families):
    sigs = {p.signature for p in trace.programs.values()}
    for fam in families:
        for cfg in trace.variants.get(fam, []):
            sigs.update(cfg.values())
    return sigs


def test_fused_logistic_fit_is_two_dispatches_plus_warm_sibling(
    fused_trace,
):
    """A single-device fused logistic fit compiles exactly 3 programs:
    materialize + cold fit + warm-start fit — and a λ grid adds ZERO."""
    assert set(fused_trace.programs) == {
        "materialize",
        "fit",
        "fit_warm",
    }
    base = {p.signature for p in fused_trace.programs.values()}
    assert len(base) == 3  # the three programs really are distinct
    with_grid = _all_signatures(fused_trace, ["lambda_grid"])
    assert with_grid == base, (
        "a λ-grid config sweep minted new fused-fit programs — the "
        "warm-start ladder now recompiles per config"
    )


def test_fused_fit_statics_recompile_as_declared(fused_trace):
    base = fused_trace.programs["fit"].signature
    for fam in ("optimizer_swap", "iteration_count"):
        sigs = {
            sig
            for cfg in fused_trace.variants[fam]
            for sig in cfg.values()
        }
        assert base not in sigs, f"{fam} no longer specializes the trace"


def test_unfused_coordinate_update_is_one_program(unfused_trace):
    """One unfused coordinate update = ONE compiled program, shared by
    the λ grid and warm starts; an optimizer swap mints exactly one
    more."""
    assert set(unfused_trace.programs) == {"coordinate_update"}
    base = unfused_trace.programs["coordinate_update"].signature
    grid = _all_signatures(unfused_trace, ["lambda_grid", "warm_start"])
    assert grid == {base}, (
        "λ / warm-start operands of the coordinate update now perturb "
        "the compile key"
    )
    swap = _all_signatures(unfused_trace, ["optimizer_swap"])
    assert len(swap - {base}) == 1


def test_census_checks_pass_on_the_real_contracts(
    fused_trace, unfused_trace
):
    contracts = {c.name: c for c in program.collect_contracts()}
    for name, trace in (
        ("fused-fit", fused_trace),
        ("unfused-coordinate-update", unfused_trace),
    ):
        findings = program.run_checks(contracts[name], trace)
        assert [f for f in findings if not f.suppressed] == []


def test_newton_kernel_shape_specialization():
    with jax.enable_x64(False):
        trace = program.build_newton_kernel()
    base = trace.programs["newton_step"].signature
    assert _all_signatures(trace, []) == {base}
    for fam in ("bucket_shape", "line_search_trials"):
        assert _all_signatures(trace, [fam]) != {base}


# ---------------------------------------------------------------------------
# compile-cache instrumentation (utils/compile_cache.cache_stats)
# ---------------------------------------------------------------------------


def test_cache_stats_counts_misses_then_hits(tmp_path):
    from photon_tpu.utils import cache_stats, enable_compilation_cache

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        assert (
            enable_compilation_cache(str(tmp_path)) == str(tmp_path)
        )
        # Everything persists, however fast it compiled.
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 0.0
        )

        @jax.jit
        def fn(x):
            return jnp.tanh(x) * 3.0 + jnp.flip(x)

        before = cache_stats()
        fn(jnp.arange(1024.0)).block_until_ready()
        after_miss = cache_stats()
        assert (
            after_miss["persistent_misses"]
            > before["persistent_misses"]
        )
        assert after_miss["entries"] > 0
        assert after_miss["bytes"] > 0
        assert after_miss["dir"] == str(tmp_path)

        # Dropping the in-memory executable cache forces the recompile
        # through the persistent cache: a HIT this time.
        jax.clear_caches()
        fn(jnp.arange(1024.0)).block_until_ready()
        after_hit = cache_stats()
        assert (
            after_hit["persistent_hits"] > after_miss["persistent_hits"]
        )
        assert 0.0 < after_hit["hit_rate"] <= 1.0
    finally:
        # "off" un-latches the cache singleton (it latched tmp_path
        # above) so later compiles in this process stop writing there;
        # restoring the config lets the next enable re-latch cleanly.
        enable_compilation_cache("off")
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", prev_min
        )


def test_cache_stats_disabled_reports_none_dir():
    from photon_tpu.utils.compile_cache import (
        cache_stats,
        enable_compilation_cache,
    )

    prev_dir = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compilation_cache("off") is None
        assert cache_stats()["dir"] is None
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)


def test_cache_dir_comes_from_the_jax_variable(tmp_path, monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set the program sets NO
    directory in code (the chip tool places the cache from outside; the
    path is part of the cache key) and cache_stats() reports it."""
    from photon_tpu.utils import compile_cache

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    outside = str(tmp_path / "outside")
    try:
        # What JAX does at import when the variable is set.
        jax.config.update("jax_compilation_cache_dir", outside)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        # The retired override must not be read any more.
        monkeypatch.setenv("PHOTON_COMPILE_CACHE", str(tmp_path / "old"))
        updates = []
        real_update = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda k, v: (updates.append(k), real_update(k, v))[1],
        )
        assert compile_cache.enable_compilation_cache() == outside
        assert "jax_compilation_cache_dir" not in updates
        assert jax.config.jax_compilation_cache_dir == outside
        assert compile_cache.cache_stats()["dir"] == outside
    finally:
        monkeypatch.undo()
        compile_cache.enable_compilation_cache("off")
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", prev_min
        )


def test_cache_dir_defaults_to_the_checkout(monkeypatch):
    """Variable unset: <checkout>/.jax_cache, the same string on every
    call (never a temporary name, a pid or a time), whatever
    PHOTON_COMPILE_CACHE says."""
    import pathlib

    from photon_tpu.utils import compile_cache

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    want = str(pathlib.Path(__file__).resolve().parents[1] / ".jax_cache")
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("PHOTON_COMPILE_CACHE", "/nonexistent/old")
        assert compile_cache.enable_compilation_cache() == want
        assert compile_cache.enable_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert compile_cache.cache_stats()["dir"] == want
    finally:
        compile_cache.enable_compilation_cache("off")
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", prev_min
        )
