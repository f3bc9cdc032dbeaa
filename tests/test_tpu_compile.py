"""The engine's Pallas kernels compile natively for a TPU v5e.

Each case compiles one registered kernel's Pallas form (``interpret=False``)
for one chip of a described ``v5e:2x2`` topology -- the TPU compiler is
installed, the chip is not needed -- at the shapes ``chip_smoke.py`` runs it
on Pallas: the deployment fleet of 8 guests x 4,194,304 base pages at
``hp_ratio`` 512 where the kernel stays on Pallas under ``auto``, else the
reduced fleet the smoke forces onto Pallas. A compile that passes proves the
kernel lowers (block tiling, VMEM budget, layouts); it says nothing about
speed or results, which the chip run checks.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import registry

# deployment fleet: 8 guests x 4,194,304 pages, hp_ratio 512, gpa_slack 0.25,
# near_fraction 0.25 -> 81,920 GPA huge pages, 16,384 of them near
HP = 512
N_GUESTS, GUEST_PAGES = 8, 4_194_304
N_GPA_HP = N_GUESTS * (GUEST_PAGES // HP) * 5 // 4
N_NEAR = N_GUESTS * (GUEST_PAGES // HP) // 4
MAX_BATCHES = 4
# the reduced fleet chip_smoke.py runs with every kernel forced onto Pallas:
# 4 guests x 131,072 pages, 65,536 accesses per guest per window
SMALL_LOGICAL, SMALL_ACCESSES, SMALL_GPA_HP = 4 * 131_072, 4 * 65_536, 4 * 320

i32, f32 = jnp.int32, jnp.float32

# name -> (kernel, [(shape, dtype)...], static kwargs)
CASES = {
    "bincount_access_histogram": (
        "bincount", [((SMALL_ACCESSES,), i32), ((SMALL_ACCESSES,), i32)],
        dict(n_bins=SMALL_LOGICAL + 1)),
    "bincount_host_histogram": (
        "bincount", [((SMALL_LOGICAL,), i32), ((SMALL_LOGICAL,), i32)],
        dict(n_bins=SMALL_GPA_HP)),
    "topk_rows_small": (
        "topk_rows", [((4, 131_072), i32)], dict(k=MAX_BATCHES * HP)),
    "topk_rows_full": (
        "topk_rows", [((N_GUESTS, GUEST_PAGES), i32)],
        dict(k=MAX_BATCHES * HP)),
    "hot_count_full": (
        "hot_count", [((N_GPA_HP * HP,), jnp.bool_)], dict(hp_ratio=HP)),
    "gather_rows_near_pool": (
        "gather_rows", [((N_NEAR * HP, 8), f32), ((N_GUESTS, HP), i32)], {}),
    "gather_rows_far_pool": (
        "gather_rows",
        [(((N_GPA_HP - N_NEAR) * HP, 8), f32), ((N_GUESTS, HP), i32)], {}),
    "consolidate_region_full": (
        "consolidate_region", [((N_GPA_HP * HP, 8), f32), ((HP,), i32)], {}),
}


@pytest.fixture(scope="module")
def one_chip():
    saved = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        try:
            from jax.experimental import topologies

            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
        if saved is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.mark.parametrize("case", sorted(CASES))
def test_pallas_kernel_compiles_for_v5e(one_chip, case):
    name, args, static = CASES[case]
    pallas = registry.get_kernel(name).pallas
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    compiled = jax.jit(
        lambda *a: pallas(*a, **static, interpret=False)).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
