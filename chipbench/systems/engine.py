"""The system under test: the program's tiering engine, driven one
``repro.core.engine.run`` call per telemetry window.

``Program(config, seed)`` builds the configuration's fleet with
``engine.build`` and writes every allocated base page's payload from the
seed (the program starts its pools at zero, which no copy could get
wrong). ``step`` runs one window; ``final`` hands the check the end state:
the guest page table, the host block table, and a sample of payloads read
back through the program's own final mapping (the check holds that mapping
equal to the reference's, page for page).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from harness import check
from harness.fleet import guests, payload_value, seed_words


class Program:
    """The engine, one ``engine.run`` call per window."""

    def __init__(self, config: dict, seed: int):
        from repro.core import engine

        self.engine = engine
        self.config = config
        self.seed = seed
        self.spec, self.state = build(config, seed)
        self.n_guests = self.spec.n_guests
        p = config["policy"]
        self.kw = dict(policy=p["host"], backend=p["telemetry"],
                       use_gpac=bool(p["use_gpac"]),
                       max_batches=int(p["max_batches"]),
                       budget=int(p["budget"]),
                       kernel_backend=p["kernel_backend"],
                       collect=("hits", "near_blocks"))

    def step(self, acc: np.ndarray) -> dict:
        self.state, series = self.engine.run(
            self.spec, self.state, self.engine.ArrayTrace(acc), **self.kw)
        return {k: series[k][0] for k in check.SERIES}

    def counters(self) -> dict:
        return {k: int(v) for k, v in self.state.stats.items()}

    def final(self) -> dict:
        gpt = np.asarray(self.state.gpt)
        bt = np.asarray(self.state.block_table)
        logical = check.payload_sample(self.config, gpt, self.seed)
        pool, row = check.payload_positions(self.config, gpt, bt, logical)
        values = read_payload(self.state, pool, row)
        return dict(gpt=gpt, block_table=bt, payload=(logical, values),
                    seed=self.seed)

    def free(self) -> None:
        self.state = None


def build(config: dict, seed: int):
    """(spec, state) of the configuration's fleet, payload written."""
    import jax
    import jax.numpy as jnp
    from repro.core import engine

    f = config["fleet"]
    specs = tuple(
        engine.GuestSpec(n_logical=int(f["pages_per_guest"]), cl=int(t["cl"]),
                         gpa_slack=float(f["gpa_slack"]), workload=t["workload"])
        for t in guests(config))
    host = engine.HostSpec(
        hp_ratio=int(f["hp_ratio"]), near_fraction=float(f["near_fraction"]),
        base_elems=int(f["base_elems"]), cl=int(f["default_cl"]),
        hot_threshold=int(f["hot_threshold"]),
        ipt_windows=int(f["ipt_windows"]), ipt_min_hits=int(f["ipt_min_hits"]),
        reconsolidate_cooldown=int(f["reconsolidate_cooldown"]))
    spec, state = engine.build(specs, host)
    cfg = spec.cfg
    R, E = cfg.hp_ratio, cfg.base_elems

    @jax.jit
    def pools(slot_owner, rmap, s0, s1):
        # slot s holds huge page slot_owner[s]; its base pages' logical ids
        # come from rmap (FREE = -1 holds zeros)
        gpa = slot_owner[:, None] * R + jnp.arange(R, dtype=jnp.int32)[None, :]
        logical = rmap[gpa]
        elem = jnp.arange(E, dtype=jnp.int32)
        val = payload_value(jnp, jnp.maximum(logical, 0)[..., None],
                            elem[None, None, :], E, s0, s1)
        val = jnp.where(logical[..., None] >= 0, val, 0.0).astype(cfg.dtype)
        return val[: cfg.n_near], val[cfg.n_near:]

    s0, s1 = (jnp.uint32(w) for w in seed_words(seed))
    near, far = pools(state.slot_owner, state.rmap, s0, s1)
    state = dataclasses.replace(state, near_pool=near, far_pool=far)
    return spec, jax.block_until_ready(state)


def read_payload(state, pool: np.ndarray, row: np.ndarray) -> np.ndarray:
    """float32[len(row), base_elems] read from the program's pools at the
    given (pool, base-page row) positions (pool 0 = near, 1 = far)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gather(near, far, pool, row):
        e = near.shape[-1]
        nr, fr = near.reshape(-1, e), far.reshape(-1, e)
        a = nr[jnp.where(pool == 0, row, 0)]
        b = fr[jnp.where(pool == 1, row, 0)]
        return jnp.where((pool == 0)[:, None], a, b)

    return np.asarray(gather(state.near_pool, state.far_pool,
                             jnp.asarray(pool, jnp.int32),
                             jnp.asarray(row, jnp.int32)))
