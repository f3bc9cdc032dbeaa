"""Multi-host runtime (DESIGN.md §17): distributed launch, the
2-process x 2-devices-each coordinated CPU matrix, and live guest
migration.

The acceptance invariant is INV-MULTIHOST-EXACT: an engine run spanning
OS processes (``jax.distributed`` + gloo CPU collectives) is bit-identical
to the single-process run on the same global mesh -- both host paths, both
trace sources, and through the churn stepper. The multi-process matrix
runs via ``repro.launch.multihost.launch`` because device count and the
collectives implementation are fixed at jax init, exactly like the forced
8-device matrix in tests/test_engine_sharded.py.
"""
import dataclasses
import os
import sys
import textwrap

import numpy as np
import pytest

from repro.core import engine, faults
from repro.core.types import FREE
from repro.launch import migration, multihost

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------
# launcher plumbing (in-process, no coordinated job)
# --------------------------------------------------------------------------
class TestLaunchUtilities:
    def test_initialize_is_noop_single_process(self):
        info = multihost.initialize(num_processes=1)
        assert info.num_processes == 1
        assert info.process_id == 0
        assert info.is_coordinator
        assert info.coordinator_address is None

    def test_initialize_requires_coordinator(self):
        with pytest.raises(ValueError, match="coordinator"):
            multihost.initialize(num_processes=2, process_id=0)

    def test_initialize_rejects_bad_process_id(self):
        with pytest.raises(ValueError, match="process_id"):
            multihost.initialize(coordinator_address="127.0.0.1:1",
                                 num_processes=2, process_id=7)

    def test_worker_env_exports_rendezvous(self):
        env = multihost.worker_env(
            {}, coordinator="127.0.0.1:9999", num_processes=2, process_id=1,
            devices_per_process=3)
        assert env[multihost.ENV_COORDINATOR] == "127.0.0.1:9999"
        assert env[multihost.ENV_NUM_PROCESSES] == "2"
        assert env[multihost.ENV_PROCESS_ID] == "1"
        assert env[multihost.ENV_CPU_COLLECTIVES] == "gloo"
        assert "device_count=3" in env["XLA_FLAGS"]
        assert env["JAX_PLATFORMS"] == "cpu"
        assert env["PYTHONPATH"].split(os.pathsep)[0] == "src"

    def test_launch_rejects_zero_processes(self):
        with pytest.raises(ValueError, match="num_processes"):
            multihost.launch("worker.py", num_processes=0)

    def test_launch_check_flags_missing_marker(self, tmp_path):
        worker = tmp_path / "w.py"
        worker.write_text("print('hello')\n")
        with pytest.raises(AssertionError, match="marker"):
            multihost.launch_check(str(worker), marker="NOPE",
                                   num_processes=1, devices_per_process=1,
                                   timeout=60)

    def test_global_guest_mesh_matches_core(self):
        import jax

        from repro.core import sharding

        a = multihost.global_guest_mesh()
        b = sharding.guest_mesh()
        if jax.device_count() == 1:
            assert a is None and b is None
        else:
            assert a.shape == b.shape


# --------------------------------------------------------------------------
# live migration (in-process: host-side protocol on replicated state)
# --------------------------------------------------------------------------
def migration_engine():
    # identical lane geometry so any pair is migration-compatible
    guests = tuple(
        engine.GuestSpec(n_logical=48, cl=4,
                         workload=["redis", "masim", "hash"][g % 3], seed=g)
        for g in range(4))
    return engine.build(
        guests, engine.HostSpec(hp_ratio=8, near_fraction=0.4,
                                base_elems=2, cl=6))


def logical_rows(spec, state, g):
    """The data guest ``g`` sees: one row per logical page via
    ``gpt -> block_table -> pools`` (the layout invariant)."""
    cfg = spec.cfg
    lo, hi = spec.logical_range(g)
    gpa = np.asarray(state.gpt[lo:hi])
    hp, sub = gpa // cfg.hp_ratio, gpa % cfg.hp_ratio
    slots = np.asarray(state.block_table)[hp]
    near, far = np.asarray(state.near_pool), np.asarray(state.far_pool)
    return np.where((slots < cfg.n_near)[:, None],
                    near[np.minimum(slots, cfg.n_near - 1), sub],
                    far[np.maximum(slots - cfg.n_near, 0), sub])


class TestMigration:
    def test_extract_release_inject_roundtrip(self):
        """A full handoff back into the same lane restores every field of
        the state bit-for-bit (payload included)."""
        spec, s0 = migration_engine()
        warm, _ = engine.run(spec, s0, engine.SynthTrace(
            n_windows=3, accesses_per_window=96))
        pkg = migration.extract_guest(spec, warm, 1)
        man = pkg.manifest
        assert man["total_bytes"] == (man["payload_bytes"]
                                      + man["mapping_bytes"]
                                      + man["telemetry_bytes"])
        rel = migration.release_guest(spec, warm, 1)
        hp_lo, hp_hi = spec.hp_range(1)
        r = spec.cfg.hp_ratio
        assert (np.asarray(rel.rmap[hp_lo * r:hp_hi * r]) == int(FREE)).all()
        back = migration.inject_guest(spec, rel, 1, pkg)
        for f in dataclasses.fields(type(warm)):
            a, b = getattr(warm, f.name), getattr(back, f.name)
            items = a.items() if isinstance(a, dict) else [(f.name, a)]
            for k, x in items:
                y = b[k] if isinstance(b, dict) else b
                np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(y), err_msg=f"roundtrip {k}")

    def test_migrate_preserves_logical_view_and_reclaims_source(self):
        spec, s0 = migration_engine()
        active = np.array([True, True, True, False])  # lane 3 is the spare
        cs = engine.init_churn(spec, s0, active=active)
        cs, _ = engine.run_churn(spec, cs, engine.SynthTrace(
            n_windows=3, accesses_per_window=96))
        before = logical_rows(spec, cs.state, 1)
        cs2, man = migration.migrate_guest(spec, cs, src=1, dst=3)
        np.testing.assert_array_equal(
            before, logical_rows(spec, cs2.state, 3),
            err_msg="guest-visible data changed across migration")
        act = np.asarray(cs2.active)
        assert not act[1] and act[3]
        hp_lo, hp_hi = spec.hp_range(1)
        r = spec.cfg.hp_ratio
        assert (np.asarray(cs2.state.rmap[hp_lo * r:hp_hi * r])
                == int(FREE)).all(), "source lane not reclaimed"
        assert man["total_bytes"] > 0
        # the stepper continues on the migrated carry (any mesh; the smoke
        # pins mesh-vs-single-process equality of this continuation)
        fs = faults.no_faults(4).crash(1, 0)
        cs3, se = engine.run_churn(spec, cs2, engine.SynthTrace(
            n_windows=3, accesses_per_window=96), faults=fs)
        assert np.asarray(se["active"])[:, 3].all()

    def test_migrate_rejects_busy_or_idle_lanes(self):
        spec, s0 = migration_engine()
        cs = engine.init_churn(spec, s0,
                               active=np.array([True, True, True, False]))
        with pytest.raises(ValueError, match="vacant"):
            migration.migrate_guest(spec, cs, src=0, dst=1)
        with pytest.raises(ValueError, match="not active"):
            migration.migrate_guest(spec, cs, src=3, dst=0)
        with pytest.raises(ValueError, match="both lane"):
            migration.migrate_guest(spec, cs, src=0, dst=0)
        with pytest.raises(TypeError, match="ChurnState"):
            migration.migrate_guest(spec, s0, src=0, dst=3)

    def test_migrate_rejects_geometry_mismatch(self):
        guests = tuple(engine.GuestSpec(n_logical=32 + 16 * (g % 2), cl=4)
                       for g in range(4))
        spec, s0 = engine.build(
            guests, engine.HostSpec(hp_ratio=8, base_elems=2, cl=6))
        cs = engine.init_churn(spec, s0,
                               active=np.array([True, False, True, False]))
        with pytest.raises(ValueError, match="geometry"):
            migration.migrate_guest(spec, cs, src=0, dst=1)

    def test_inject_requires_vacant_destination(self):
        spec, s0 = migration_engine()
        warm, _ = engine.run(spec, s0, engine.SynthTrace(
            n_windows=2, accesses_per_window=96))
        pkg = migration.extract_guest(spec, warm, 0)
        with pytest.raises(ValueError, match="vacant|holds allocated"):
            migration.inject_guest(spec, warm, 2, pkg)

    def test_quiesce_resume_flip_only_the_mask(self):
        spec, s0 = migration_engine()
        cs = engine.init_churn(spec, s0)
        q = migration.quiesce(cs, 2)
        assert not bool(np.asarray(q.active)[2])
        np.testing.assert_array_equal(np.asarray(q.state.rmap),
                                      np.asarray(cs.state.rmap))
        back = migration.resume(q, 2)
        np.testing.assert_array_equal(np.asarray(back.active),
                                      np.asarray(cs.active))


# --------------------------------------------------------------------------
# the coordinated 2-process x 2-devices matrix (INV-MULTIHOST-EXACT)
# --------------------------------------------------------------------------
MULTIPROCESS_CHECK = textwrap.dedent("""
    from repro.launch import multihost

    info = multihost.initialize()

    import jax
    import numpy as np

    from repro.core import engine, faults, sharding

    assert jax.process_count() == 2, jax.process_count()
    guests = tuple(
        engine.GuestSpec(n_logical=48 + 16 * (g % 2),
                         cl=(None if g % 3 == 0 else 3 + g % 5),
                         workload=["redis", "masim", "hash"][g % 3], seed=g)
        for g in range(5))  # 5 guests on 4 shards: padding + raggedness
    spec, state = engine.build(
        guests, engine.HostSpec(hp_ratio=8, near_fraction=0.4,
                                base_elems=2, cl=6))
    mesh = multihost.global_guest_mesh()
    assert sharding.mesh_size(mesh) == 4, mesh

    sources = dict(
        array=engine.ArrayTrace(
            engine.guest_traces(spec, n_windows=3, accesses_per_window=96)),
        synth=engine.SynthTrace(n_windows=3, accesses_per_window=96),
    )
    for src_name, source in sources.items():
        s_ref, a = engine.run(spec, state, source)
        for host_sharded in (False, True):
            s_sh, b = engine.run_sharded(spec, state, source, mesh=mesh,
                                         host_sharded=host_sharded)
            for k in a:
                np.testing.assert_array_equal(
                    a[k], b[k],
                    err_msg=f"{src_name}, host_sharded={host_sharded}: {k}")
            for x, y in zip(jax.tree_util.tree_leaves(s_ref),
                            jax.tree_util.tree_leaves(s_sh)):
                np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(y),
                    err_msg=f"{src_name}, host_sharded={host_sharded}")
            print("OK", src_name, host_sharded, flush=True)

    # churn stepper with faults across the two processes
    fs = faults.no_faults(5).crash(1, 1).restart(2, 1)
    synth = engine.SynthTrace(n_windows=4, accesses_per_window=96)
    cs0 = engine.init_churn(spec, state)
    ref_cs, ref = engine.run_churn(spec, cs0, synth, faults=fs)
    sh_cs, sh = engine.run_churn(spec, cs0, synth, faults=fs, mesh=mesh)
    for k in ref:
        np.testing.assert_array_equal(ref[k], sh[k], err_msg=f"churn: {k}")
    for x, y in zip(jax.tree_util.tree_leaves(ref_cs),
                    jax.tree_util.tree_leaves(sh_cs)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg="churn state")
    print("OK churn", flush=True)
    print(f"[{info.process_id}] MATRIX OK", flush=True)
""")


class TestMultiprocessMatrix:
    def test_two_process_mesh_matches_single_process(self, tmp_path):
        """INV-MULTIHOST-EXACT acceptance matrix: ragged guests on a
        2-process x 2-device global mesh, array + synth sources, both host
        paths, and the churn stepper -- every check asserted inside each
        coordinated worker against that worker's own single-process run."""
        worker = tmp_path / "matrix_worker.py"
        worker.write_text(MULTIPROCESS_CHECK)
        results = multihost.launch_check(
            str(worker), marker="MATRIX OK", num_processes=2,
            devices_per_process=2, cwd=ROOT, timeout=600)
        assert len(results) == 2
        for r in results:
            assert r.stdout.count("OK") >= 6, r.stdout


# --------------------------------------------------------------------------
# collective-volume accounting + the pod migration protocol (§17)
# --------------------------------------------------------------------------
class TestCollectiveAccounting:
    def test_replicated_run_records_merge_window_bytes(self):
        from repro.core import sharding

        jax = pytest.importorskip("jax")
        if jax.local_device_count() < 2:
            pytest.skip("needs >= 2 devices for a mesh")
        spec, state = migration_engine()
        mesh = sharding.guest_mesh(2)
        synth = engine.SynthTrace(n_windows=2, accesses_per_window=64)
        sharding.reset_collective_bytes()
        assert sharding.collective_bytes() == {}
        engine.run_sharded(spec, state, synth, mesh=mesh,
                           host_sharded=False)
        rec = sharding.collective_bytes()
        assert rec.get("merge_window", 0) > 0
        # the ownership-merge payload carries at least the mapping arrays
        cfg = spec.cfg
        assert rec["merge_window"] >= 4 * (cfg.n_logical + cfg.n_gpa)

    def test_host_sharded_run_records_exchange_and_exit(self):
        from repro.core import sharding

        jax = pytest.importorskip("jax")
        if jax.local_device_count() < 2:
            pytest.skip("needs >= 2 devices for a mesh")
        spec, state = migration_engine()
        mesh = sharding.guest_mesh(2)
        synth = engine.SynthTrace(n_windows=2, accesses_per_window=64)
        sharding.reset_collective_bytes()
        # a replicated state in: partitioned once, gathered once at the end
        engine.run_sharded(spec, state, synth, mesh=mesh, host_sharded=True)
        rec = sharding.collective_bytes()
        assert rec.get("host_exchange", 0) > 0
        assert rec.get("host_gather", 0) > 0
        sharding.reset_collective_bytes()
        assert sharding.collective_bytes() == {}


class TestPodMigration:
    def test_run_pod_migrations_payload(self, tmp_path, monkeypatch):
        """fig9_at_scale.run_pod(migrations=...) drives the §17 protocol:
        manifests, host-state report and collective accounting ride the
        payload, and every lane is active after the handoffs."""
        benchmarks = pytest.importorskip("benchmarks.fig9_at_scale")
        monkeypatch.chdir(tmp_path)  # common.save writes experiments/ here
        out = benchmarks.run_pod(n_guests=4, logical_per_guest=64,
                                 n_windows=4, accesses=64, migrations=1,
                                 mesh=None)
        assert out["n_migrations"] == 1
        res = out["memtierd"]
        assert len(res["migrations"]) == 1
        man = res["migrations"][0]
        assert man["src"] == 0 and man["dst"] == 4
        assert man["total_bytes"] == (man["payload_bytes"]
                                      + man["mapping_bytes"]
                                      + man["telemetry_bytes"])
        # the handoff preserves fleet occupancy: 4 lanes active throughout
        assert res["active_per_window"] == [4, 4, 4, 4]
        assert res["active_final"] == 4
        assert out["host_state"]["n_devices"] == 1
        assert (tmp_path / "experiments" / "benchmarks"
                / "fig9_at_pod_scale_migration.json").exists()
