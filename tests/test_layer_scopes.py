"""Every device op of a layer function carries its engine layer scope.

``engine.LAYERS`` names the layers a profiler trace attributes device time
to: each window body wraps its calls in ``jax.named_scope``, and an op's
layer is the outermost ``tier.*`` component of its HLO op-name path. This
compiles one window of a small GPAC fleet and of a small host-only fleet
through every window body (``engine._window``, ``engine._churn_window``,
``sharding._sharded_window``, ``sharding._churn_sharded_window`` and the
host-partitioned ``sharding._host_sharded_group``) and checks every op of
the compiled HLO whose source line lies in a layer module. The bodies run
in one subprocess on four forced host devices (the sharded ones need a
mesh; the device count is fixed when JAX starts), which dumps each
compiled module as text.
"""
import functools
import json
import os
import subprocess
import sys

import pytest

from repro.core import engine

BODIES = ("window", "churn_window", "sharded_window", "churn_sharded_window",
          "host_sharded_group")
FLEETS = ("gpac", "host")
# modules whose ops must carry a layer scope (the sharded exchange and the
# churn controller live in sharding.py and faults.py and may stay unscoped)
LAYER_SOURCES = ("/repro/core/address_space.py", "/repro/core/telemetry.py",
                 "/repro/core/filter.py", "/repro/core/consolidator.py",
                 "/repro/core/tiering.py", "/repro/core/tiers.py",
                 "/repro/kernels/")

SCRIPT = r'''
import json, os, re, sys
from pathlib import Path
import jax
import numpy as np
from repro.core import engine, sharding

dump = Path(sys.argv[1])
sources = tuple(json.loads(sys.argv[2]))


def sections(lines, name):
    out = {}
    if name not in lines:
        return out
    for line in lines[lines.index(name) + 1:]:
        if not line.strip():
            break
        k, v = line.split(" ", 1)
        out[int(k)] = v
    return out


def ops(text):
    """(op_name, stack frame id, source file) of every op of the program:
    the source is the innermost user frame of the op's stack. A reducer
    body or a parameter carries only a bare name, not a path from the
    jitted function, and is skipped."""
    lines = text.splitlines()
    files = sections(lines, "FileNames")
    locs = sections(lines, "FileLocations")
    frames = sections(lines, "StackFrames")

    def source(fid):
        loc = int(re.search(r"file_location_id=(\d+)", frames[fid]).group(1))
        f = int(re.search(r"file_name_id=(\d+)", locs[loc]).group(1))
        return files[f].strip('"')

    for m in re.finditer(
            r'metadata=\{op_name="(jit\([^"]*)"(?: stack_frame_id=(\d+))?', text):
        fid = int(m.group(2) or 0)
        yield m.group(1), fid, source(fid) if fid else ""


def tier_of(op_name):
    return next((c for c in op_name.split("/") if c.startswith("tier.")), None)


def check(text):
    """Ops of the layer modules, the outermost scope of each, and those
    outside every scope. JAX traces a jitted helper (``jnp.where``'s
    ``jit(_where)``) once per argument types and reuses that trace at every
    call site: the op-name path is the call site's, but the source is where
    the helper was first traced. So an unscoped op inside such a helper
    whose very stack frame also appears under a scope is another call site
    of a helper first traced in a layer, not a layer op."""
    found = [o for o in ops(text) if any(s in o[2] for s in sources)]
    scoped = {fid for name, fid, _ in found if tier_of(name)}
    scopes, bad = set(), []
    for name, fid, src in found:
        tier = tier_of(name)
        if tier:
            scopes.add(tier)
        elif not ("/jit(" in name and fid in scoped):
            bad.append([name, src.split("/src/")[-1]])
    return len(found), scopes, bad


def fleet():
    guests = tuple(engine.GuestSpec(n_logical=2048, cl=8) for _ in range(4))
    host = engine.HostSpec(hp_ratio=32, near_fraction=0.25, cl=8)
    spec, state = engine.build(guests, host)
    acc = np.random.default_rng(0).integers(-1, 2048, (4, 1, 256)).astype(np.int32)
    return spec, state, acc


def drive(body, spec, state, acc, use_gpac):
    kw = dict(use_gpac=use_gpac, collect=("hits", "near_blocks", "tco"))
    mesh = sharding.guest_mesh(4)
    if body == "window":
        engine.run(spec, state, acc, **kw)
    elif body == "churn_window":
        engine.run_churn(spec, engine.init_churn(spec, state), acc, **kw)
    elif body == "sharded_window":
        engine.run_sharded(spec, state, acc, mesh=mesh, host_sharded=False, **kw)
    elif body == "churn_sharded_window":
        engine.run_churn(spec, engine.init_churn(spec, state), acc, mesh=mesh, **kw)
    else:
        engine.run_sharded(spec, state, acc, mesh=mesh, host_sharded=True, **kw)


result = {}
for name in json.loads(sys.argv[3]):
    body, kind = name.split(":")
    spec, state, acc = fleet()
    engine.init_churn(spec, state)
    jax.clear_caches()  # helpers are traced afresh by this case's program
    seen = set(dump.glob("*after_optimizations.txt"))
    drive(body, spec, state, acc, kind == "gpac")
    checked, bad, scopes = 0, [], set()
    for f in sorted(set(dump.glob("*after_optimizations.txt")) - seen):
        n, s, b = check(f.read_text())
        checked, bad, scopes = checked + n, bad + b, scopes | s
    result[name] = dict(checked=checked, bad=bad[:20], scopes=sorted(scopes))
print(json.dumps(result))
'''


@functools.lru_cache(maxsize=None)
def compiled_scopes(tmp):
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS=(f"--xla_force_host_platform_device_count=4 "
                   f"--xla_dump_to={tmp} --xla_dump_hlo_as_text"),
    )
    cases = [f"{b}:{f}" for b in BODIES for f in FLEETS]
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, tmp, json.dumps(LAYER_SOURCES),
         json.dumps(cases)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def scopes(tmp_path_factory):
    return compiled_scopes(str(tmp_path_factory.mktemp("hlo_dump")))


@pytest.mark.parametrize("fleet", FLEETS)
@pytest.mark.parametrize("body", BODIES)
def test_layer_ops_carry_their_scope(scopes, body, fleet):
    got = scopes[f"{body}:{fleet}"]
    assert got["checked"] > 0
    assert got["bad"] == [], "ops of layer modules outside any tier.* scope"
    assert set(got["scopes"]) <= set(engine.LAYERS)
    want = {"tier.record", "tier.tick", "tier.roll"}
    if fleet == "gpac":
        want |= {"tier.classify", "tier.score", "tier.select", "tier.copy"}
    assert set(got["scopes"]) >= want


CACHED = r'''
import sys
import jax
import jax.numpy as jnp
from repro.launch import compile_cache

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
compile_cache.enable()


@jax.jit
def f(x):
    with jax.named_scope(sys.argv[1]):
        return (x * 2).sum()


print(sorted({c for c in f.lower(jnp.ones(8)).compile().as_text().split("/")
              if c.startswith("tier.")}))
'''


def test_cached_executable_keeps_its_scopes(tmp_path):
    """A program that differs from a cached one in its scopes alone compiles
    anew: the executable carries its own op names, not the cached one's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    got = []
    for scope in ("tier.record", "tier.roll"):
        proc = subprocess.run([sys.executable, "-c", CACHED, scope], env=env,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        got.append(json.loads(proc.stdout.strip().splitlines()[-1]
                              .replace("'", '"')))
    assert got == [["tier.record"], ["tier.roll"]]


@pytest.mark.parametrize("spelled", ("resolved", "as_imported"))
def test_cache_key_cuts_the_checkout_root(spelled):
    """Source paths lose the checkout's root, however it is spelled, so a
    moved checkout keys its programs as this one does; a path outside the
    checkout keeps its own."""
    import re
    from pathlib import Path

    from repro.launch import compile_cache

    root = Path(compile_cache.__file__).parents[3]
    root = root.resolve() if spelled == "resolved" else Path(
        os.path.abspath(root))
    cut = functools.partial(re.sub, compile_cache.CHECKOUT_PREFIX, "")
    assert cut(str(root / "src/repro/core/filter.py")) == \
        "/src/repro/core/filter.py"
    assert cut(str(root / "chipbench/run.py")) == "/chipbench/run.py"
    assert cut("/venv/lib/site-packages/jax/_src/api.py") == \
        "/venv/lib/site-packages/jax/_src/api.py"
