"""The comparison that decides ``correct``.

A run's system under test hands back, for every window it ran, each guest's
near and far hits and near blocks held, and at the end its guest page table,
host block table and a sample of page payloads. The configuration's plain
reference replays the same windows from the seed; every number compared is
a count of disagreements, with the limit the configuration gives (0: the
comparison is exact).
"""
from __future__ import annotations

import time

import numpy as np

from harness import fleet as fleet_mod
from harness.traffic import Feed

SERIES = ("near_hits", "far_hits", "near_blocks")
SAMPLE = 1 << 20  # payload pages read back per run


def payload_sample(config: dict, gpt: np.ndarray, seed: int) -> np.ndarray:
    """Logical pages whose payload the check reads back: up to half of
    ``SAMPLE`` pages that consolidation moved (found from the final page
    table), the rest drawn uniformly, all from the seed."""
    geo = fleet_mod.geometry(config)
    f = config["fleet"]
    n, G = int(f["pages_per_guest"]), int(f["guests"])
    hp_guest = geo["n_gpa_hp"] // G
    logical = np.arange(geo["n_logical"], dtype=np.int64)
    start = (logical // n) * hp_guest * geo["hp_ratio"] + logical % n
    moved = np.flatnonzero(gpt != start)
    del logical, start
    rng = np.random.default_rng([seed, 2])
    size = min(SAMPLE, geo["n_logical"])
    if moved.size > size // 2:
        moved = rng.choice(moved, size // 2, replace=False)
    rest = rng.integers(0, geo["n_logical"], size - moved.size)
    return np.concatenate([moved, rest]).astype(np.int64)


def payload_positions(config: dict, gpt: np.ndarray, bt: np.ndarray,
                      logical: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(pool, row) of each page's payload under the mapping (gpt, bt):
    pool 0 = near, 1 = far; row counts base pages within the pool."""
    geo = fleet_mod.geometry(config)
    R, n_near = geo["hp_ratio"], geo["n_near"]
    gpa = gpt[logical].astype(np.int64)
    slot = bt[gpa // R].astype(np.int64)
    far = slot >= n_near
    row = np.where(far, slot - n_near, slot) * R + gpa % R
    return far.astype(np.int32), row


def expected_payload(config: dict, logical: np.ndarray, seed: int) -> np.ndarray:
    E = int(config["fleet"]["base_elems"])
    s0, s1 = (np.uint32(w) for w in fleet_mod.seed_words(seed))
    return fleet_mod.payload_value(
        np, logical[:, None], np.arange(E)[None, :], E, s0, s1)


def replay(ref, traffic, n_windows: int, workers: int) -> tuple[dict, float]:
    """The reference's series over windows 0..n_windows-1, and its seconds."""
    t0 = time.perf_counter()
    out = {k: [] for k in SERIES}
    moved = []
    with Feed(lambda w: ref.prepare(traffic.window(w)), 0, workers,
              depth=2 * workers) as feed:
        for _ in range(n_windows):
            _, pre, _ = feed.get()
            r = ref.step(pre)
            for k in SERIES:
                out[k].append(np.asarray(r[k]))
            moved.append(r["moved"])
    series = {k: np.stack(v) if v else np.zeros((0, ref.geo.n_guests), np.int64)
              for k, v in out.items()}
    series["moved"] = np.asarray(moved, np.int64)
    return series, time.perf_counter() - t0


def compare(config: dict, got: dict, want: dict, ref, final: dict) -> dict:
    """Counts of disagreement between the system's outputs (``got``: its
    per-window series; ``final``: its end state) and the reference's."""
    limits = config["check"]["limits"]
    n = min(len(got["near_hits"]), len(want["near_hits"]))
    missing = abs(len(got["near_hits"]) - len(want["near_hits"]))

    def rows(key):
        g, w = np.asarray(got[key][:n]), np.asarray(want[key][:n])
        return int((g != w).sum()) + missing * ref.geo.n_guests

    nums = dict(
        hits_mismatch=rows("near_hits") + rows("far_hits"),
        near_blocks_mismatch=rows("near_blocks"),
        gpt_mismatch=int((final["gpt"] != ref.gpt).sum()),
        block_table_mismatch=int((final["block_table"] != ref.bt).sum()),
    )
    if "payload" in final:
        logical, values = final["payload"]
        want_vals = expected_payload(config, logical, final["seed"])
        nums["payload_mismatch"] = int(
            (values.view(np.uint32) != want_vals.view(np.uint32)).any(axis=1).sum())
    return {k: dict(value=v, limit=limits[k]) for k, v in nums.items()}


def passed(numbers: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values())
