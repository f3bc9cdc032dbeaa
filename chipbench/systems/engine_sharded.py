"""The system under test on a host of several chips: the program's tiering
engine with its state kept partitioned over the chips, driven one
``repro.core.engine.run_sharded`` call per telemetry window.

``Program(config, seed)`` builds the configuration's fleet in place on a
mesh of its ``chips`` (``engine.build(..., mesh=)``: each chip builds its
own guests' part, so the whole state never exists on one chip) and writes
every allocated base page's payload from the seed, on the chip that holds
the page. ``step`` runs one window on the partitioned state; ``final``
hands the check the guest page table and the host block table, assembled
on the host from every chip's part, and a sample of payloads read back
through the program's own final mapping on the chips that hold them.

A program whose ``engine.build`` has no mesh form cannot hold such a fleet:
``Program`` stops at once, before it allocates anything.
"""
from __future__ import annotations

import dataclasses
import inspect

import numpy as np

from harness import check
from harness.fleet import guests, payload_value, seed_words


class Program:
    """The engine on a mesh, one ``engine.run_sharded`` call per window."""

    def __init__(self, config: dict, seed: int):
        from repro.core import engine, sharding

        if "mesh" not in inspect.signature(engine.build).parameters:
            raise SystemExit(
                "engine_sharded: this program's engine.build has no mesh "
                "form, so it cannot build the fleet partitioned over the "
                "chips")
        self.engine, self.sharding = engine, sharding
        self.config = config
        self.seed = seed
        self.mesh = sharding.guest_mesh(int(config["chips"]))
        self.spec, self.state = build(config, seed, self.mesh)
        self.n_guests = self.spec.n_guests
        p = config["policy"]
        self.kw = dict(mesh=self.mesh, policy=p["host"],
                       backend=p["telemetry"], use_gpac=bool(p["use_gpac"]),
                       max_batches=int(p["max_batches"]),
                       budget=int(p["budget"]),
                       kernel_backend=p["kernel_backend"],
                       collect=("hits", "near_blocks"))

    def step(self, acc: np.ndarray) -> dict:
        self.state, series = self.engine.run_sharded(
            self.spec, self.state, self.engine.ArrayTrace(acc), **self.kw)
        return {k: series[k][0] for k in check.SERIES}

    def counters(self) -> dict:
        # each chip counts its own share
        return {k: int(np.asarray(v).sum()) for k, v in self.state.stats.items()}

    def final(self) -> dict:
        host = self.sharding.host_arrays(self.spec, self.state)
        gpt, bt = host["gpt"], host["block_table"]
        logical = check.payload_sample(self.config, gpt, self.seed)
        values = self.sharding.read_pages(self.spec, self.mesh, self.state,
                                          gpt[logical])
        return dict(gpt=gpt, block_table=bt, payload=(logical, values),
                    seed=self.seed)

    def free(self) -> None:
        self.state = None


def build(config: dict, seed: int, mesh):
    """(spec, partitioned state) of the configuration's fleet on ``mesh``,
    payload written."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import engine, sharding

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
    spec, state = engine.build(specs, host, mesh=mesh)
    cfg = spec.cfg
    R, E = cfg.hp_ratio, cfg.base_elems
    part = sharding.host_partition(spec, sharding.mesh_size(mesh))

    def pages(old, rmap, l_lo, s0, s1):
        # a chip's payload row h holds its huge page h's base pages, whose
        # chip-local logical ids rmap gives (FREE = -1 holds zeros)
        local = rmap.reshape(-1, R)
        logical = jnp.where(local >= 0, local + l_lo[0], -1)
        elem = jnp.arange(E, dtype=jnp.int32)
        val = payload_value(jnp, jnp.maximum(logical, 0)[..., None],
                            elem[None, None, :], E, s0, s1)
        return jnp.where(logical[..., None] >= 0, val, 0.0).astype(old.dtype)

    fill = jax.jit(jax.shard_map(
        pages, mesh=mesh, in_specs=(P("guest"), P("guest"), P("guest"), P(), P()),
        out_specs=P("guest")), donate_argnums=0)
    s0, s1 = (jnp.uint32(w) for w in seed_words(seed))
    payload = fill(state.payload, state.rmap, jnp.asarray(part.l_lo, jnp.int32),
                   s0, s1)
    state = dataclasses.replace(state, payload=payload)
    return spec, jax.block_until_ready(state)
