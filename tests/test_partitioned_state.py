"""The host-partitioned state kept partitioned between calls.

``engine.run_sharded(host_sharded=True)`` handed a ``PartitionedState``
returns one: nothing replicated is built on the way in or out. Driven one
window per call, as a deployment drives it, it must equal ``engine.run``'s
calls bit for bit (INV-MULTIHOST-EXACT): each call's hits and near blocks,
and afterwards the page table, the block table and every page's payload
read back, with GPAC on and off and guest counts the mesh does not divide.
``engine.build(..., mesh=)`` builds that state in place, each device its own
part, and its explicit gather equals ``engine.build``'s state. The cases run
in one subprocess on four forced CPU devices (the device count is fixed
when JAX starts).
"""
import functools
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r'''
import json, sys, traceback
import jax, jax.numpy as jnp, numpy as np
from repro.core import address_space as asp, engine, sharding
from repro.core.types import PartitionedState

assert jax.device_count() == 4, jax.device_count()
mesh = sharding.guest_mesh(4)
HOST = engine.HostSpec(hp_ratio=16, near_fraction=0.4, base_elems=2, cl=6)


def guests(n, ragged):
    return tuple(
        engine.GuestSpec(
            n_logical=64 + 16 * (g % 4) if ragged else 96,
            cl=None if g % 3 == 0 else 3 + g % 5,
            gpa_slack=0.25 + 0.25 * (g % 3) if ragged else 0.25,
            workload=["redis", "masim", "hash"][g % 3], seed=g)
        for g in range(n))


def same(a, b, what):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), what
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


def bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


def calls(n, ragged, use_gpac):
    g = guests(n, ragged)
    spec, ref = engine.build(g, HOST)
    cfg = spec.cfg
    logical = jnp.arange(cfg.n_logical, dtype=jnp.int32)
    fill = (jnp.arange(cfg.n_logical * cfg.base_elems, dtype=jnp.float32)
            * 0.5 + 1.0).reshape(cfg.n_logical, cfg.base_elems)
    ref = asp.write_logical(cfg, ref, logical, fill)
    ps = sharding.partition_state(spec, mesh, ref)
    traces = engine.guest_traces(spec, n_windows=4, accesses_per_window=128)
    for w in range(traces.shape[1]):
        acc = traces[:, w:w + 1]
        ref, a = engine.run(spec, ref, acc, use_gpac=use_gpac)
        ps, b = engine.run_sharded(spec, ps, acc, mesh=mesh,
                                   use_gpac=use_gpac)
        assert isinstance(ps, PartitionedState), type(ps)
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k], err_msg=f"{k} {w}")
    if use_gpac:
        assert int(ref.stats["consolidated_pages"]) > 0
    assert int(ref.stats["promoted_blocks"]) > 0
    host = sharding.host_arrays(spec, ps)
    np.testing.assert_array_equal(host["gpt"], np.asarray(ref.gpt))
    np.testing.assert_array_equal(host["block_table"],
                                  np.asarray(ref.block_table))
    got = sharding.read_pages(spec, mesh, ps, host["gpt"])
    want = asp.read_logical(cfg, ref, logical)
    np.testing.assert_array_equal(bits(got), bits(want))
    same(sharding.gather_state(spec, mesh, ps), ref, "gathered state")


def build(n, ragged):
    g = guests(n, ragged)
    spec, ref = engine.build(g, HOST)
    spec_m, ps = engine.build(g, HOST, mesh=mesh)
    assert spec_m == spec
    assert isinstance(ps, PartitionedState), type(ps)
    part = sharding.host_partition(spec, 4)
    for shard in ps.gpt.addressable_shards:  # each device holds its part
        assert shard.data.shape == (part.l_loc,), shard.data.shape
    same(sharding.gather_state(spec, mesh, ps), ref, "built state")
    same(sharding.partition_state(spec, mesh, ref), ps, "partitioned state")


CASES = {
    "calls-gpac-6": lambda: calls(6, False, True),
    "calls-host-6": lambda: calls(6, False, False),
    "calls-gpac-ragged-7": lambda: calls(7, True, True),
    "calls-host-ragged-7": lambda: calls(7, True, False),
    "build-6": lambda: build(6, False),
    "build-ragged-7": lambda: build(7, True),
}
out = {}
for name in json.loads(sys.argv[1]):
    try:
        CASES[name]()
        out[name] = "OK"
    except Exception:
        out[name] = traceback.format_exc()[-3000:]
print(json.dumps(out))
'''

CASES = ("calls-gpac-6", "calls-host-6", "calls-gpac-ragged-7",
         "calls-host-ragged-7", "build-6", "build-ragged-7")


@functools.lru_cache(maxsize=None)
def results() -> dict:
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, json.dumps(CASES)], env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", CASES)
def test_partitioned_state_matches_run(case):
    assert results()[case] == "OK", results()[case]
