"""Kernel-backend test suite (DESIGN.md §4, §16).

Three layers of pinning:

* per-kernel sweeps: every Pallas kernel in interpret mode vs the pure-jnp
  reference across shapes and dtypes (the engine hot-path kernels pin
  bit-for-bit; the attention kernels allclose);
* the registry itself: the PR-2 idiom (duplicates raise, unknown names list
  the live set), the ``KernelSpec`` triad (pallas == ref == numpy oracle on
  each entry's self-describing example), and the ``use_pallas=`` deprecation
  shims;
* the engine: every driver (``run``, ``run_sharded`` both host paths,
  ``run_churn``) bit-identical under ``kernel_backend="pallas"`` vs
  ``"xla"`` (INV-KERNEL-BACKEND-EXACT).
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import registry
from repro.kernels.consolidate import ops as cons_ops
from repro.kernels.consolidate import ref as cons_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.flash_attention import kernel as fa_kernel
from repro.kernels.histogram import ops as hg_ops
from repro.kernels.hotness_scan import ops as hs_ops
from repro.kernels.hotness_scan import ref as hs_ref
from repro.kernels.paged_attention import ops as pa_ops
from repro.kernels.paged_attention import ref as pa_ref
from repro.kernels.tiered_lookup import ops as tl_ops
from repro.kernels.tiered_lookup import ref as tl_ref
from repro.kernels.topk import ops as tk_ops


def rand(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: dict(rtol=1e-6, atol=1e-6), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


class TestConsolidateKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n_rows,elems,hp_ratio", [(64, 128, 16), (256, 256, 32), (32, 512, 8)])
    def test_gather_sweep(self, rng, n_rows, elems, hp_ratio, dtype):
        rows = rand(rng, (n_rows, elems), dtype)
        ids = np.full((hp_ratio,), -1, np.int32)
        k = rng.integers(1, hp_ratio + 1)
        ids[:k] = rng.choice(n_rows, size=k, replace=False)
        ids = jnp.asarray(ids)
        got = cons_ops.consolidate_region(rows, ids, kernel_backend="pallas")
        want = cons_ref.consolidate_region_ref(rows, ids)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_scatter_sweep(self, rng, dtype):
        for n_rows, elems, hp_ratio in [(64, 128, 16), (48, 256, 8)]:
            dst = rand(rng, (n_rows, elems), dtype)
            region = rand(rng, (hp_ratio, elems), dtype)
            ids = np.full((hp_ratio,), -1, np.int32)
            k = rng.integers(1, hp_ratio + 1)
            ids[:k] = rng.choice(n_rows, size=k, replace=False)
            ids = jnp.asarray(ids)
            got = cons_ops.scatter_region(dst, region, ids, kernel_backend="pallas")
            want = cons_ref.scatter_region_ref(dst, region, ids)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_scatter_row0_target(self, rng):
        """A real write to row 0 must win over padded-slot redirection."""
        dst = rand(rng, (16, 128), jnp.float32)
        region = rand(rng, (8, 128), jnp.float32)
        ids = jnp.asarray([3, 0, -1, -1, 5, -1, -1, -1], jnp.int32)
        got = cons_ops.scatter_region(dst, region, ids, kernel_backend="pallas")
        want = cons_ref.scatter_region_ref(dst, region, ids)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestHotnessScan:
    @pytest.mark.parametrize("n_hp,hp_ratio", [(7, 16), (32, 128), (100, 512), (1, 8)])
    def test_sweep(self, rng, n_hp, hp_ratio):
        bits = jnp.asarray(rng.integers(0, 2, size=(n_hp * hp_ratio,)), jnp.int32)
        got = hs_ops.hot_count(bits, hp_ratio, kernel_backend="pallas")
        want = hs_ref.hot_count_ref(bits, hp_ratio)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_matches_core_telemetry(self, rng):
        """Kernel agrees with the core's jnp hot_subpages_per_hp on real state."""
        from repro.core import GpacConfig, init_state, telemetry, address_space as asp

        cfg = GpacConfig(n_logical=96, hp_ratio=16, n_gpa_hp=10, n_near=4, base_elems=2, cl=8)
        state = init_state(cfg)
        ids = jnp.asarray(rng.integers(0, cfg.n_logical, size=40), jnp.int32)
        state = asp.record_accesses(cfg, state, ids)
        hot = telemetry.hot_mask(cfg, state, "ipt")
        want = telemetry.hot_subpages_per_hp(cfg, state, hot)
        got = telemetry.hot_subpages_per_hp(cfg, state, hot, kernel_backend="pallas")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestHistogram:
    @pytest.mark.parametrize("n_bins,k", [(7, 16), (128, 1000), (4096, 257), (1, 8)])
    def test_sweep(self, rng, n_bins, k):
        ids = jnp.asarray(rng.integers(-2, n_bins + 3, size=(k,)), jnp.int32)
        w = jnp.asarray(rng.integers(0, 5, size=(k,)), jnp.int32)
        got = hg_ops.bincount(ids, w, n_bins, kernel_backend="pallas")
        want = hg_ops.bincount(ids, w, n_bins, kernel_backend="xla")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_matches_core_histogram(self, rng):
        """Kernel path agrees with the core access_histogram on real ids."""
        from repro.core import GpacConfig, address_space as asp

        cfg = GpacConfig(n_logical=96, hp_ratio=16, n_gpa_hp=10, n_near=4, base_elems=2, cl=8)
        ids = jnp.asarray(rng.integers(-3, cfg.n_logical + 3, size=(4, 40)), jnp.int32)
        want = asp.access_histogram(cfg, ids, kernel_backend="xla")
        got = asp.access_histogram(cfg, ids, kernel_backend="pallas")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestTopK:
    @pytest.mark.parametrize("rows,width,k", [(4, 64, 8), (16, 300, 300), (1, 8, 1)])
    def test_matches_lax_top_k(self, rng, rows, width, k):
        """Ties resolve to the lowest column, exactly like jax.lax.top_k."""
        mat = jnp.asarray(rng.integers(-1, 5, size=(rows, width)), jnp.int32)
        got_v, got_i = tk_ops.topk_rows(mat, k, kernel_backend="pallas")
        want_v, want_i = jax.lax.top_k(mat, k)
        np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))

    @pytest.mark.parametrize(
        "shape,k", [((4, 64), 8), ((16, 300), 300), ((1, 8), 1), ((2, 4096), 2048),
                    ((257,), 31)])
    def test_tie_exact_path_matches_oracle(self, rng, shape, k):
        """The order rebuilt from values alone (what a TPU runs) keeps
        lax.top_k's lowest-index tie-break, sentinel scores included."""
        from repro.kernels.topk import ref as tk_ref

        x = rng.integers(-1, 5, size=shape).astype(np.int32)
        x.reshape(-1)[:2] = np.iinfo(np.int32).min + 1
        got_v, got_i = jax.jit(tk_ref._top_k_tie_exact, static_argnums=1)(
            jnp.asarray(x), k)
        want_i = np.argsort(-x.astype(np.int64), axis=-1, kind="stable")[..., :k]
        np.testing.assert_array_equal(np.asarray(got_i), want_i)
        np.testing.assert_array_equal(
            np.asarray(got_v), np.take_along_axis(x, want_i, axis=-1))


class TestTieredLookup:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n_logical,d,k", [(64, 128, 32), (256, 256, 100)])
    def test_sweep(self, rng, n_logical, d, k, dtype):
        n_rows = n_logical + 32
        rows = rand(rng, (n_rows, d), dtype)
        fused = jnp.asarray(rng.permutation(n_rows)[:n_logical], jnp.int32)
        ids = rng.integers(-2, n_logical + 2, size=(k,)).astype(np.int32)
        got = tl_ops.tiered_lookup(rows, fused, jnp.asarray(ids), kernel_backend="pallas")
        want = tl_ref.tiered_lookup_ref(rows, fused, jnp.asarray(ids))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_multidim_ids(self, rng):
        rows = rand(rng, (64, 128), jnp.float32)
        fused = jnp.arange(64, dtype=jnp.int32)
        ids = jnp.asarray(rng.integers(0, 64, size=(4, 8)), jnp.int32)
        got = tl_ops.tiered_lookup(rows, fused, ids, kernel_backend="pallas")
        assert got.shape == (4, 8, 128)
        want = tl_ref.tiered_lookup_ref(rows, fused, ids)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_gather_rows_multidim(self, rng):
        rows = rand(rng, (32, 16), jnp.float32)
        ids = jnp.asarray(rng.integers(0, 32, size=(3, 5)), jnp.int32)
        got = tl_ops.gather_rows(rows, ids, kernel_backend="pallas")
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(rows)[np.asarray(ids)])


class TestPagedAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,KVH,G,hd,page,pps", [(2, 2, 4, 64, 16, 4), (3, 1, 8, 128, 8, 3), (1, 4, 1, 64, 32, 2)]
    )
    def test_sweep(self, rng, B, KVH, G, hd, page, pps, dtype):
        n_pages = B * pps + 4
        q = rand(rng, (B, KVH, G, hd), dtype)
        k = rand(rng, (KVH, n_pages, page, hd), dtype)
        v = rand(rng, (KVH, n_pages, page, hd), dtype)
        btab = jnp.asarray(
            rng.permutation(n_pages)[: B * pps].reshape(B, pps), jnp.int32
        )
        lens = jnp.asarray(rng.integers(1, pps * page + 1, size=(B,)), jnp.int32)
        got = pa_ops.paged_attention(q, k, v, btab, lens, kernel_backend="pallas")
        want = pa_ref.paged_attention_ref(q, k, v, btab, lens)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
            atol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
        )

    def test_len_zero_sequence_is_finite(self, rng):
        q = rand(rng, (1, 1, 2, 64), jnp.float32)
        k = rand(rng, (1, 4, 8, 64), jnp.float32)
        v = rand(rng, (1, 4, 8, 64), jnp.float32)
        btab = jnp.zeros((1, 2), jnp.int32)
        lens = jnp.zeros((1,), jnp.int32)
        got = pa_ops.paged_attention(q, k, v, btab, lens, kernel_backend="pallas")
        assert np.isfinite(np.asarray(got)).all()


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("B,H,KVH,S,hd", [(2, 4, 2, 128, 64), (1, 8, 8, 256, 64), (1, 6, 2, 128, 128)])
    def test_sweep(self, rng, B, H, KVH, S, hd, causal, dtype):
        q = rand(rng, (B, H, S, hd), dtype)
        k = rand(rng, (B, KVH, S, hd), dtype)
        v = rand(rng, (B, KVH, S, hd), dtype)
        got = fa_ops.gqa_attention(q, k, v, causal=causal, kernel_backend="pallas",
                                   block_q=64, block_k=64)
        want = fa_ops.gqa_attention(q, k, v, causal=causal, kernel_backend="xla")
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=3e-2 if dtype == jnp.bfloat16 else 1e-5,
            atol=3e-2 if dtype == jnp.bfloat16 else 1e-5,
        )

    def test_matches_naive_softmax(self, rng):
        """Oracle itself cross-checked against an independent naive formula."""
        B, H, S, hd = 1, 2, 32, 16
        q = rand(rng, (B, H, S, hd), jnp.float32)
        k = rand(rng, (B, H, S, hd), jnp.float32)
        v = rand(rng, (B, H, S, hd), jnp.float32)
        want = fa_ops.gqa_attention(q, k, v, causal=True, kernel_backend="xla")
        s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k)) / np.sqrt(hd)
        mask = np.tril(np.ones((S, S), bool))
        s = np.where(mask, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        naive = np.einsum("bhqk,bhkd->bhqd", p, np.asarray(v))
        np.testing.assert_allclose(np.asarray(want), naive, rtol=1e-5, atol=1e-5)

    def test_kernel_direct_group_fold(self, rng):
        """Direct kernel call with group>1 vs ref with the same fold."""
        BH, S, hd, G = 2, 64, 64, 2
        q = rand(rng, (BH, S * G, hd), jnp.float32)
        k = rand(rng, (BH, S, hd), jnp.float32)
        v = rand(rng, (BH, S, hd), jnp.float32)
        got = fa_kernel.flash_attention(
            q, k, v, causal=True, group=G, block_q=64, block_k=64, interpret=True
        )
        want = fa_ref.flash_attention_ref(q, k, v, causal=True, group=G)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# the registry itself (DESIGN.md §4: the eighth registry, PR-2 idiom)
# --------------------------------------------------------------------------
# engine hot-path kernels: integer sums and pure row copies, pinned
# bit-for-bit; the attention kernels reassociate float accumulations and
# pin allclose instead
_EXACT = {
    "bincount", "topk_rows", "hot_count", "gather_rows", "tiered_lookup",
    "consolidate_region", "scatter_region",
}


class TestRegisteredKernelEquivalence:
    """Every registry entry's self-describing example: pallas == ref
    (== numpy oracle where one is registered)."""

    @pytest.mark.parametrize("name", registry.kernel_names())
    def test_pallas_matches_ref(self, name):
        spec = registry.get_kernel(name)
        assert spec.example is not None, f"{name}: registry entry lacks example"
        args, kwargs = spec.example()
        got = registry.dispatch(name, "pallas", *args, **kwargs)
        want = registry.dispatch(name, "xla", *args, **kwargs)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            if name in _EXACT:
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
            else:
                np.testing.assert_allclose(
                    np.asarray(g, np.float32), np.asarray(w, np.float32),
                    rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize(
        "name", [n for n in registry.kernel_names()
                 if registry.get_kernel(n).oracle is not None])
    def test_ref_matches_oracle(self, name):
        spec = registry.get_kernel(name)
        args, kwargs = spec.example()
        want = spec.oracle(*args, **kwargs)
        got = spec.ref(*args, **kwargs)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


class TestKernelRegistry:
    def test_expected_kernels_registered(self):
        assert registry.kernel_names() == (
            "bincount", "consolidate_region", "gather_rows", "gqa_attention",
            "hot_count", "paged_attention", "scatter_region", "tiered_lookup",
            "topk_rows",
        )

    def test_duplicate_registration_raises(self, monkeypatch):
        monkeypatch.setattr(registry, "_KERNELS", dict(registry._KERNELS))
        registry.register_kernel(
            "test_dup", pallas=lambda *a, **k: None, ref=lambda *a: None)
        with pytest.raises(ValueError, match="already registered"):
            registry.register_kernel(
                "test_dup", pallas=lambda *a, **k: None, ref=lambda *a: None)

    def test_unknown_kernel_lists_live_set(self):
        with pytest.raises(ValueError, match="bincount"):
            registry.get_kernel("no_such_kernel")
        with pytest.raises(ValueError, match="no_such_kernel"):
            registry.dispatch("no_such_kernel", "xla")

    def test_unknown_backend_lists_choices(self):
        with pytest.raises(ValueError, match="cuda"):
            registry.resolve_backend("cuda")
        with pytest.raises(ValueError, match="pallas"):
            registry.resolve_backend("")

    def test_auto_resolves_to_xla_on_cpu(self):
        # this container has no TPU and the CI kernel job overrides via env
        import os

        if os.environ.get("REPRO_KERNEL_BACKEND"):
            assert registry.resolve_backend("auto") == os.environ[
                "REPRO_KERNEL_BACKEND"]
        else:
            assert registry.resolve_backend("auto") == "xla"

    def test_engine_rejects_unknown_backend(self):
        from repro.core import engine

        spec, s0 = engine.build([16], engine.HostSpec(hp_ratio=4, cl=2))
        with pytest.raises(ValueError, match="kernel backend"):
            engine.run(spec, s0, engine.SynthTrace(1, 8), kernel_backend="avx")


class TestUsePallasShims:
    """The deprecated ``use_pallas=`` tri-state warns and maps onto
    ``kernel_backend=`` (True -> pallas, False -> xla, None -> auto)."""

    def test_shim_warns_and_matches(self, rng):
        bits = jnp.asarray(rng.integers(0, 2, size=(64,)), jnp.int32)
        with pytest.warns(DeprecationWarning, match="use_pallas"):
            got = hs_ops.hot_count(bits, 16, use_pallas=True)
        want = hs_ops.hot_count(bits, 16, kernel_backend="pallas")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_shim_false_is_xla(self, rng):
        rows = rand(rng, (8, 4), jnp.float32)
        ids = jnp.asarray([1, 3, -1, -1], jnp.int32)
        with pytest.warns(DeprecationWarning, match="use_pallas"):
            got = cons_ops.consolidate_region(rows, ids, use_pallas=False)
        want = cons_ops.consolidate_region(rows, ids, kernel_backend="xla")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_shim_none_is_auto(self, rng):
        rows = rand(rng, (8, 4), jnp.float32)
        fused = jnp.arange(8, dtype=jnp.int32)
        ids = jnp.asarray([0, 5], jnp.int32)
        with pytest.warns(DeprecationWarning, match="use_pallas"):
            got = tl_ops.tiered_lookup(rows, fused, ids, use_pallas=None)
        want = tl_ops.tiered_lookup(rows, fused, ids, kernel_backend="auto")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_no_warning_without_shim(self, rng):
        bits = jnp.asarray(rng.integers(0, 2, size=(64,)), jnp.int32)
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            hs_ops.hot_count(bits, 16)
            hs_ops.hot_count(bits, 16, kernel_backend="pallas")


# --------------------------------------------------------------------------
# engine-level backend equivalence (INV-KERNEL-BACKEND-EXACT, DESIGN.md §16)
# --------------------------------------------------------------------------
def _assert_trees_equal(a, b, msg):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=msg)


def _assert_series_equal(a, b, msg):
    assert set(a) == set(b), msg
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=f"{msg}:{k}")


@pytest.fixture(scope="module")
def small_engine():
    from repro.core import engine

    spec, s0 = engine.build(
        [engine.GuestSpec(n_logical=96, cl=6), engine.GuestSpec(n_logical=64)],
        engine.HostSpec(hp_ratio=8, near_fraction=0.5, base_elems=2, cl=4),
    )
    return spec, s0, engine.SynthTrace(n_windows=5, accesses_per_window=64)


class TestEngineBackendEquivalence:
    """kernel_backend="pallas" (interpret on CPU) is bit-identical to
    "xla" on every driver — the test-side pin of INV-KERNEL-BACKEND-EXACT."""

    def test_run(self, small_engine):
        from repro.core import engine

        spec, s0, src = small_engine
        sx, ox = engine.run(spec, s0, src, kernel_backend="xla")
        sp, op = engine.run(spec, s0, src, kernel_backend="pallas")
        _assert_trees_equal(sx, sp, "run state diverged")
        _assert_series_equal(ox, op, "run series diverged")

    @pytest.mark.parametrize("host_sharded", [False, True])
    def test_run_sharded(self, small_engine, host_sharded):
        from repro.core import engine, sharding

        spec, s0, src = small_engine
        mesh = sharding.guest_mesh(1)
        sx, ox = engine.run(spec, s0, src, kernel_backend="xla")
        sp, op = engine.run_sharded(
            spec, s0, src, mesh=mesh, host_sharded=host_sharded,
            kernel_backend="pallas")
        _assert_trees_equal(sx, sp, f"run_sharded(hs={host_sharded}) diverged")
        _assert_series_equal(ox, op, f"run_sharded(hs={host_sharded}) series")

    @pytest.mark.skipif(
        jax.device_count() < 2,
        reason="multi-device mesh needs --xla_force_host_platform_device_count")
    @pytest.mark.parametrize("host_sharded", [False, True])
    def test_run_sharded_multidevice(self, small_engine, host_sharded):
        from repro.core import engine, sharding

        spec, s0, src = small_engine
        mesh = sharding.guest_mesh(min(jax.device_count(), 8))
        sx, ox = engine.run(spec, s0, src, kernel_backend="xla")
        sp, op = engine.run_sharded(
            spec, s0, src, mesh=mesh, host_sharded=host_sharded,
            kernel_backend="pallas")
        _assert_trees_equal(sx, sp, "multi-device pallas state diverged")
        _assert_series_equal(ox, op, "multi-device pallas series diverged")

    def test_run_churn(self, small_engine):
        from repro.core import engine

        spec, s0, src = small_engine
        cx, ex = engine.run_churn(
            spec, engine.init_churn(spec), src, kernel_backend="xla")
        cp, ep = engine.run_churn(
            spec, engine.init_churn(spec), src, kernel_backend="pallas")
        _assert_trees_equal(cx, cp, "run_churn state diverged")
        _assert_series_equal(ex, ep, "run_churn series diverged")

    def test_spec_level_backend_equals_driver_kwarg(self, small_engine):
        import dataclasses

        from repro.core import engine

        spec, s0, src = small_engine
        pl_spec = dataclasses.replace(spec, kernel_backend="pallas")
        sa, oa = engine.run(pl_spec, s0, src)
        sb, ob = engine.run(spec, s0, src, kernel_backend="pallas")
        _assert_trees_equal(sa, sb, "spec-level backend diverged from kwarg")
        _assert_series_equal(oa, ob, "spec-level backend series diverged")
