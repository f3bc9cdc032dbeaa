"""The check that decides ``correct``, driven through a whole run of a small
copy of each cell on the CPU (the harness's look for a chip skipped): a
sound program passes, and each fault planted under the timed path, and the
control, come out not correct."""
from __future__ import annotations

import dataclasses
import time

import numpy as np
import pytest

from bench_helpers import small_copy

from harness import cell, check, control, layout

SECONDS = 0.5
SEED = 2**35 + 77


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    # a run turns JAX's persistent compilation cache on; leave the process
    # as it was for the tests that share it
    before = jax.config.jax_compilation_cache_dir
    yield small_copy(tmp_path_factory.mktemp("bench"))
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def no_chip_look(monkeypatch):
    import jax

    monkeypatch.setattr(cell, "tpu_devices", lambda count: jax.devices())


def run(root, name, fault=None, seed=SEED):
    c = layout.resolve_cell(name, root)
    program = layout.system_module(c).Program
    system = program if fault is None else type(fault.__name__, (fault, program), {})
    out = cell.run_cell(c, seed, SECONDS, False, t_start=time.perf_counter(),
                        system=system)
    return out.result


# Faults planted under the timed path, each a mixin over the configuration's
# Program (``super()`` is the sound step).
class StateUnchanged:
    """A step that returns its state unchanged."""

    def step(self, acc):
        before = self.state
        out = super().step(acc)
        self.state = before
        return out


class HalfBatch:
    """Half of the batch left out: the second half of the guests' accesses
    never reach the engine."""

    def step(self, acc):
        acc = acc.copy()
        acc[acc.shape[0] // 2:] = -1
        return super().step(acc)


class AnswerAltered:
    """One answer altered where it is produced: guest 0's near hits of the
    third window."""

    windows = 0

    def step(self, acc):
        out = dict(super().step(acc))
        self.windows += 1
        if self.windows == 3:
            out["near_hits"] = out["near_hits"] + np.eye(len(out["near_hits"]),
                                                         dtype=out["near_hits"].dtype)[0]
        return out


class CopyDropped:
    """Pages and blocks are remapped but their payload is not moved."""

    def step(self, acc):
        near, far = self.state.near_pool, self.state.far_pool
        out = super().step(acc)
        self.state = dataclasses.replace(self.state, near_pool=near, far_pool=far)
        return out


@pytest.mark.parametrize("name", ["gpac28x16g.kv", "host28x16g.kv"])
def test_sound_run_is_correct(root, name):
    r = run(root, name)
    assert r["correct"], r["checks"]
    assert all(v["value"] == 0 for v in r["checks"].values())
    assert list(r)[-1] == "checks"
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"guest_windows_per_s", "peak_hbm_gib", "setup_s"}


@pytest.mark.parametrize("name", ["gpac28x16g.kv", "host28x16g.kv"])
@pytest.mark.parametrize("fault", [StateUnchanged, HalfBatch, AnswerAltered])
def test_fault_is_not_correct(root, name, fault):
    r = run(root, name, fault)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("name", ["gpac28x16g.kv", "host28x16g.kv"])
def test_dropped_copy_is_not_correct(root, name):
    r = run(root, name, CopyDropped)
    assert not r["correct"]
    assert r["checks"]["payload_mismatch"]["value"] > 0


@pytest.mark.parametrize("name", ["gpac28x16g.kv", "host28x16g.kv"])
@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_is_not_correct(root, name, seed):
    nums = control.readings(layout.resolve_cell(name, root), seed, n_windows=12)
    assert not check.passed(nums), nums
