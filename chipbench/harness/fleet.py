"""A fleet's sizes and payload, reckoned from a configuration file alone.

``payload_value`` is the one definition of what a page holds: the system
under test writes it into every allocated base page at set-up, and the
check compares the pages it reads back with it.
"""
from __future__ import annotations

import dataclasses

import numpy as np

_M1, _M2, _M3 = 0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35


def seed_words(seed: int) -> tuple[int, int]:
    return seed & 0xFFFFFFFF, ((seed >> 32) & 0xFFFFFFFF) ^ 0x5BD1E995


def payload_value(xp, logical, elem, base_elems: int, s0, s1):
    """float32 payload of element ``elem`` of logical page ``logical``: a
    32-bit hash of (page, element, seed words ``s0``, ``s1``), kept below
    2**24 so it is an exact float. ``xp`` is numpy or jax.numpy; both give
    the same bits."""
    u = xp.uint32
    x = (logical.astype(u) * u(base_elems) + elem.astype(u)) ^ s0
    x = x * u(_M1)
    x = x ^ (x >> u(16))
    x = x * u(_M2)
    x = x ^ (x >> u(13))
    x = (x ^ s1) * u(_M3)
    x = x ^ (x >> u(16))
    return (x >> u(8)).astype(xp.float32)


def guests(config: dict) -> list[dict]:
    """Per guest: its tenant kind and CL (tenants repeat in order)."""
    f = config["fleet"]
    t = f["tenants"]
    return [t[g % len(t)] for g in range(int(f["guests"]))]


def geometry(config: dict) -> dict:
    """Sizes of the fleet's state, reckoned from the configuration alone."""
    f = config["fleet"]
    R, n, G = int(f["hp_ratio"]), int(f["pages_per_guest"]), int(f["guests"])
    need = -(-n // R)
    hp_guest = need + max(2, int(need * float(f["gpa_slack"])))
    H = G * hp_guest
    n_near = min(max(1, int(float(f["near_fraction"]) * need * G)), H - 1)
    return dict(n_logical=G * n, n_gpa_hp=H, n_gpa=H * R, n_near=n_near,
                hp_ratio=R, base_elems=int(f["base_elems"]))


def device_bytes(config: dict) -> dict:
    """Bytes of the engine's state on the device, array by array, reckoned
    from the configuration's sizes (f32 payload, int32 tables, uint8
    histories)."""
    g = geometry(config)
    N, H, NG = g["n_logical"], g["n_gpa_hp"], g["n_gpa"]
    payload = NG * g["base_elems"] * 4
    arrays = dict(
        gpt=4 * N, rmap=4 * NG, block_table=4 * H, slot_owner=4 * H,
        near_pool=g["n_near"] * g["hp_ratio"] * g["base_elems"] * 4,
        far_pool=payload - g["n_near"] * g["hp_ratio"] * g["base_elems"] * 4,
        guest_counts=4 * N, ipt_hist=N, host_counts=4 * H, host_hist=H,
        last_touch_epoch=4 * H, region_epoch=4 * H, epoch=4, stats=9 * 4)
    return dict(arrays=arrays, state=sum(arrays.values()))
