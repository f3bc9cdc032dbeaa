"""The vectorized multi-tenant engine is a pure perf refactor: these tests pin
it bit-for-bit against the seed per-guest/per-window reference formulation
(kept as ``*_reference``), and pin ``consolidate_pages`` against the seed
full-pool-concatenation data copy it replaced."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import address_space as asp
from repro.core import consolidator, gpac, simulate, telemetry
from repro.core.address_space import dataclasses_replace
from repro.core.types import FREE, GpacConfig, init_state
from repro.data import traces as tr


def assert_states_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def small_multi_guest(n_guests=3):
    return simulate.make_multi_guest(
        n_guests=n_guests, logical_per_guest=128, hp_ratio=16,
        near_fraction=0.3, base_elems=2, cl=8,
    )


def guest_traces(n_guests=3, n_windows=6, k=256):
    return np.stack([
        tr.generate(tr.TraceSpec("redis", 128, 16, n_windows, k, seed=g))
        for g in range(n_guests)
    ])


class TestMultiGuestEngineEquivalence:
    @pytest.mark.parametrize("use_gpac", [False, True])
    @pytest.mark.parametrize("policy", ["memtierd", "autonuma", "tpp"])
    def test_engine_matches_reference(self, policy, use_gpac):
        traces = guest_traces()
        mg, s0 = small_multi_guest()
        ref_state, ref_series = simulate.run_multi_guest_reference(
            mg, s0, traces, policy=policy, use_gpac=use_gpac)
        new_state, new_series = simulate.run_multi_guest(
            mg, s0, traces, policy=policy, use_gpac=use_gpac)
        assert_states_equal(ref_state, new_state)
        assert set(ref_series) == set(new_series)
        for k in ref_series:
            np.testing.assert_array_equal(ref_series[k], new_series[k], err_msg=k)

    def test_single_window_matches_reference(self):
        traces = guest_traces(n_windows=1)
        mg, s0 = small_multi_guest()
        acc = jnp.asarray(traces[:, 0])
        ref_state, ref_out = simulate.multi_guest_window_reference(mg, s0, acc)
        new_state, new_out = simulate.multi_guest_window(mg, s0, acc)
        assert_states_equal(ref_state, new_state)
        for k in ref_out:
            np.testing.assert_array_equal(
                np.asarray(ref_out[k]), np.asarray(new_out[k]), err_msg=k)

    def test_windows_per_step_chunking_is_invisible(self):
        traces = guest_traces(n_windows=7)
        mg, s0 = small_multi_guest()
        full_state, full_series = simulate.run_multi_guest(mg, s0, traces)
        for wps in (1, 3, 100):
            st, series = simulate.run_multi_guest(
                mg, s0, traces, windows_per_step=wps)
            assert_states_equal(full_state, st)
            for k in full_series:
                np.testing.assert_array_equal(full_series[k], series[k], err_msg=k)

    def test_zero_windows_returns_empty_series(self):
        mg, s0 = small_multi_guest()
        empty = np.zeros((mg.n_guests, 0, 256), np.int32)
        ref_state, ref_series = simulate.run_multi_guest_reference(mg, s0, empty)
        new_state, new_series = simulate.run_multi_guest(mg, s0, empty)
        assert_states_equal(ref_state, new_state)
        for k in ref_series:
            np.testing.assert_array_equal(ref_series[k], new_series[k], err_msg=k)
        cfg = GpacConfig(n_logical=256, hp_ratio=16, base_elems=2, cl=8)
        st, series = gpac.run_windows(
            cfg, init_state(cfg), jnp.zeros((0, 64), jnp.int32))
        assert series == []

    def test_localize_all_matches_per_guest(self):
        mg, _ = small_multi_guest()
        acc = jnp.asarray(guest_traces(n_windows=1)[:, 0])
        acc = acc.at[:, :5].set(-1)  # padding passthrough
        batched = mg.localize_all(acc)
        for g in range(mg.n_guests):
            np.testing.assert_array_equal(
                np.asarray(batched[g]), np.asarray(mg.localize(g, acc[g])))


class TestRunWindowsEquivalence:
    @pytest.mark.parametrize("use_gpac", [False, True])
    def test_fused_matches_reference(self, use_gpac):
        cfg = GpacConfig(n_logical=512, hp_ratio=16, base_elems=2, cl=8)
        trace = jnp.asarray(tr.generate(tr.TraceSpec("redis", 512, 16, 7, 256, seed=1)))
        ref_state, ref_series = gpac.run_windows_reference(
            cfg, init_state(cfg), trace, use_gpac=use_gpac)
        new_state, new_series = gpac.run_windows(
            cfg, init_state(cfg), trace, use_gpac=use_gpac)
        assert_states_equal(ref_state, new_state)
        assert ref_series == new_series  # identical dicts incl. python types
        chunk_state, chunk_series = gpac.run_windows(
            cfg, init_state(cfg), trace, use_gpac=use_gpac, windows_per_step=3)
        assert_states_equal(ref_state, chunk_state)
        assert ref_series == chunk_series


# --------------------------------------------------------------------------
# consolidate_pages: zero-copy dual-pool gather vs the seed concat data copy
# --------------------------------------------------------------------------
def _seed_consolidate_pages(cfg, state, pages, hp_range=None):
    """The seed data-copy formulation: materializes [near_pool; far_pool] as
    one row space per call. Kept here as the regression oracle."""
    pages = pages.astype(jnp.int32)
    valid = (pages >= 0) & (pages < cfg.n_logical)
    region = asp.alloc_free_huge_region(cfg, state, hp_range)
    ok = region >= 0
    n_sel = valid.sum()
    safe_pages = jnp.where(valid, pages, 0)
    old_gpa = state.gpt[safe_pages]
    new_gpa = region * cfg.hp_ratio + jnp.arange(cfg.hp_ratio, dtype=jnp.int32)
    do_move = valid & ok
    src_slot = state.block_table[old_gpa // cfg.hp_ratio]
    src_off = old_gpa % cfg.hp_ratio
    rows = jnp.concatenate(
        [state.near_pool.reshape(-1, cfg.base_elems),
         state.far_pool.reshape(-1, cfg.base_elems)], axis=0)
    payload = rows[jnp.where(do_move, src_slot * cfg.hp_ratio + src_off, 0)]
    dst_slot = state.block_table[jnp.maximum(region, 0)]
    dst_off = jnp.arange(cfg.hp_ratio, dtype=jnp.int32)
    near_idx = jnp.where(do_move & (dst_slot < cfg.n_near), dst_slot, cfg.n_near)
    far_idx = jnp.where(
        do_move & (dst_slot >= cfg.n_near), dst_slot - cfg.n_near, cfg.n_far)
    near_pool = state.near_pool.at[near_idx, dst_off].set(payload, mode="drop")
    far_pool = state.far_pool.at[far_idx, dst_off].set(payload, mode="drop")
    gpt = state.gpt.at[jnp.where(do_move, pages, cfg.n_logical)].set(
        new_gpa, mode="drop")
    rmap = state.rmap.at[jnp.where(do_move, old_gpa, cfg.n_gpa)].set(FREE, mode="drop")
    rmap = rmap.at[jnp.where(do_move, new_gpa, cfg.n_gpa)].set(
        safe_pages, mode="drop")
    region_epoch = state.region_epoch.at[jnp.maximum(region, 0)].set(
        jnp.where(ok, state.epoch, state.region_epoch[jnp.maximum(region, 0)]))
    moved = do_move.sum()
    stats = dict(state.stats)
    stats["consolidated_pages"] = stats["consolidated_pages"] + moved.astype(jnp.int32)
    stats["consolidation_calls"] = stats["consolidation_calls"] + jnp.where(
        n_sel > 0, 1, 0).astype(jnp.int32)
    stats["consolidation_enomem"] = stats["consolidation_enomem"] + jnp.where(
        (n_sel > 0) & ~ok, 1, 0).astype(jnp.int32)
    stats["copied_bytes"] = stats["copied_bytes"] + (
        moved.astype(jnp.int32) * cfg.base_bytes)
    stats["tlb_shootdowns"] = stats["tlb_shootdowns"] + jnp.where(
        moved > 0, 1, 0).astype(jnp.int32)
    return dataclasses_replace(
        state, gpt=gpt, rmap=rmap, near_pool=near_pool, far_pool=far_pool,
        region_epoch=region_epoch, stats=stats)


class TestConsolidateNoPoolConcat:
    def _state(self, cfg, seed=0):
        fill = jax.random.normal(
            jax.random.PRNGKey(seed), (cfg.n_logical, cfg.base_elems), cfg.dtype)
        state = init_state(cfg, fill=fill)
        # scatter some placement so sources span both tiers
        from repro.core import tiering
        far = jnp.arange(cfg.n_near, cfg.n_gpa_hp, dtype=jnp.int32)[: cfg.n_near]
        near = jnp.arange(cfg.n_near, dtype=jnp.int32)[: far.shape[0]]
        return tiering.swap_blocks(cfg, state, far, near, jnp.int32(far.shape[0] // 2))

    @pytest.mark.parametrize("hp_range", [None, (30, 40)])
    def test_output_unchanged_vs_seed_concat_path(self, hp_range):
        cfg = GpacConfig(n_logical=512, hp_ratio=16, base_elems=2, cl=8)
        state = self._state(cfg)
        pages = jnp.asarray(
            list(range(3, 512, 37)) + [-1, 600, -1], jnp.int32)[: cfg.hp_ratio]
        pages = jnp.pad(pages, (0, cfg.hp_ratio - pages.shape[0]), constant_values=-1)
        ref = _seed_consolidate_pages(cfg, state, pages, hp_range)
        new = consolidator.consolidate_pages(cfg, state, pages, hp_range)
        assert_states_equal(ref, new)
        assert int(new.stats["consolidated_pages"]) > 0  # the move happened

    def test_batches_unchanged_vs_seed_concat_path(self):
        cfg = GpacConfig(n_logical=512, hp_ratio=16, base_elems=2, cl=8)
        state = self._state(cfg, seed=3)
        batches = jnp.stack([
            jnp.arange(0, 512, 33, jnp.int32)[: cfg.hp_ratio],
            jnp.full((cfg.hp_ratio,), -1, jnp.int32),
        ])
        ref = state
        for row in batches:
            ref = _seed_consolidate_pages(cfg, ref, row)
        new = consolidator.consolidate_batches(cfg, state, batches)
        assert_states_equal(ref, new)

    def test_no_pool_sized_concatenate_in_jaxpr(self):
        cfg = GpacConfig(n_logical=512, hp_ratio=16, base_elems=2, cl=8)
        state = init_state(cfg)
        pages = jnp.full((cfg.hp_ratio,), -1, jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda st, p: consolidator.consolidate_pages(cfg, st, p))(state, pages)
        pool_rows = cfg.n_near * cfg.hp_ratio  # smaller pool's row count

        def walk(jx):
            for eqn in jx.eqns:
                if eqn.primitive.name == "concatenate":
                    for v in eqn.outvars:
                        rows = v.aval.shape[0] if v.aval.shape else 0
                        assert rows < pool_rows, (
                            f"pool-sized concatenate resurfaced: {v.aval.shape}")
                for v in eqn.params.values():
                    if isinstance(v, jax.extend.core.ClosedJaxpr):
                        walk(v.jaxpr)
                    elif isinstance(v, jax.extend.core.Jaxpr):
                        walk(v)

        walk(jaxpr.jaxpr)


# --------------------------------------------------------------------------
# satellite pins: popcount + Fig. 2 statistic kernel dispatch
# --------------------------------------------------------------------------
class TestRecordAccessesAggregated:
    def test_large_batch_matches_chunked_small_batches(self):
        cfg = GpacConfig(n_logical=1024, hp_ratio=16, base_elems=2, cl=8)
        rng = np.random.default_rng(0)
        ids = rng.integers(-8, cfg.n_logical, size=4096).astype(np.int32)
        # one big call takes the aggregated histogram path...
        assert ids.size * 2 >= cfg.n_logical
        big = asp.record_accesses(cfg, init_state(cfg), jnp.asarray(ids))
        # ...many small calls take the per-access scatter path
        small = init_state(cfg)
        for chunk in ids.reshape(32, 128):
            assert chunk.size * 2 < cfg.n_logical
            small = asp.record_accesses(cfg, small, jnp.asarray(chunk))
        assert_states_equal(big, small)


class TestTelemetrySatellites:
    def test_popcount_u8_matches_bit_loop(self):
        x = jnp.arange(256, dtype=jnp.uint8)
        ref = np.array([bin(i).count("1") for i in range(256)], np.int32)
        got = telemetry._popcount_u8(x)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), ref)

    def test_accessed_subpages_matches_reshape_sum(self):
        cfg = GpacConfig(n_logical=256, hp_ratio=16, base_elems=2, cl=8)
        state = init_state(cfg)
        state = asp.record_accesses(
            cfg, state, jnp.arange(0, 256, 5, dtype=jnp.int32))
        got = telemetry.accessed_subpages_per_hp(cfg, state)
        acc = state.guest_counts > 0
        acc_gpa = jnp.where(state.rmap >= 0, acc[jnp.maximum(state.rmap, 0)], False)
        ref = acc_gpa.reshape(cfg.n_gpa_hp, cfg.hp_ratio).sum(axis=1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
