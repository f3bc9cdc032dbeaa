"""Plain reference of one fleet of guests under GPAC and host tiering.

Straight numpy from the semantics the configuration states, written apart
from the program (it imports nothing of it) and used only once a run's
window has closed. One telemetry window, in order:

1. translate: each access's logical page -> GPA page (guest page table) ->
   huge page -> slot (host block table); slots below ``n_near`` are near.
   The access counts as a near or far hit by the placement at access time.
2. record: base-page counts for the guest, huge-page counts for the host.
3. GPAC (when on), per guest inside its own segments: hot = accessed in at
   least ``ipt_min_hits`` of the last ``ipt_windows`` windows, this one
   included. A hot page is a candidate when its huge page holds fewer than
   the guest's CL hot pages and was not consolidated into within
   ``reconsolidate_cooldown`` windows. Candidates rank by (count this
   window * 256 + windows-accessed, page id ascending); the first
   ``max_batches * hp_ratio`` form ``max_batches`` batches of ``hp_ratio``.
   Batch b moves its pages, column i to offset i, into the guest's first
   fully free huge page (none: nothing moves), which is stamped with the
   window's epoch.
4. host tick (``memtierd``): the hottest allocated far huge pages with a
   positive score swap with the coldest near ones (unallocated first),
   pairwise while the far score beats the near one, up to ``budget``; then
   up to ``budget`` cold (score 0) allocated near pages swap with free far
   ones. Score = count this window * 256 + windows-accessed; ties go to
   the lowest huge-page id.
5. roll: shift every accessed bit into its history and clear the counts.

Payload follows the page it belongs to, so the reference tracks none: a
logical page's data sits at the slot and offset its final mapping gives.

``tie="high"`` breaks every tie towards the highest id instead: the control
that breaks the stated tie order.
"""
from __future__ import annotations

import numpy as np

FREE = -1
_POP8 = np.array([bin(i).count("1") for i in range(256)], np.int32)


class Geometry:
    """Sizes of the fleet, from the configuration's ``fleet`` block."""

    def __init__(self, fleet: dict):
        self.R = int(fleet["hp_ratio"])
        tenants = fleet["tenants"]
        self.n_guests = int(fleet["guests"])
        self.pages = [int(fleet["pages_per_guest"])] * self.n_guests
        self.cl = [int(tenants[g % len(tenants)]["cl"])
                   for g in range(self.n_guests)]
        slack = float(fleet["gpa_slack"])
        need = [-(-p // self.R) for p in self.pages]
        size = [n + max(2, int(n * slack)) for n in need]
        self.lo = np.concatenate([[0], np.cumsum(self.pages)]).astype(np.int64)
        self.hp_lo = np.concatenate([[0], np.cumsum(size)]).astype(np.int64)
        self.N = int(self.lo[-1])
        self.H = int(self.hp_lo[-1])
        self.n_near = min(max(1, int(float(fleet["near_fraction"]) * sum(need))),
                          self.H - 1)
        self.ipt_windows = int(fleet["ipt_windows"])
        self.ipt_min_hits = int(fleet["ipt_min_hits"])
        self.cooldown = int(fleet["reconsolidate_cooldown"])


def top_ids(mask: np.ndarray, key: np.ndarray, k: int, tie: str) -> np.ndarray:
    """Ids where ``mask`` holds, by ``key`` descending then id (ascending
    for ``tie="low"``, descending for ``"high"``), at most ``k`` of them."""
    ids = np.flatnonzero(mask)
    if tie == "high":
        ids = ids[::-1]
    keys = key[ids]
    if ids.size > k:
        kth = np.partition(keys, ids.size - k)[ids.size - k]
        above = keys > kth
        tied = np.flatnonzero(keys == kth)[: k - int(above.sum())]
        take = above
        take[tied] = True
        ids, keys = ids[take], keys[take]
    return ids[np.argsort(-keys, kind="stable")]


class Fleet:
    """The reference's state: guest page tables, host block table and the
    telemetry both keep."""

    def __init__(self, fleet: dict, policy: dict, tie: str = "low"):
        if policy["host"] != "memtierd":
            raise ValueError(
                f"the reference knows the host policy 'memtierd' only, "
                f"not {policy['host']!r}")
        if policy["telemetry"] != "ipt":
            raise ValueError(
                f"the reference knows the telemetry 'ipt' only, not "
                f"{policy['telemetry']!r}")
        self.geo = g = Geometry(fleet)
        self.use_gpac = bool(policy["use_gpac"])
        self.budget = int(policy["budget"])
        self.max_batches = int(policy["max_batches"])
        self.tie = tie
        R = g.R
        gpt = np.empty(g.N, np.int32)
        rmap = np.full(g.H * R, FREE, np.int32)
        for i in range(g.n_guests):
            gpa = g.hp_lo[i] * R + np.arange(g.pages[i], dtype=np.int64)
            gpt[g.lo[i]:g.lo[i + 1]] = gpa
            rmap[gpa] = np.arange(g.lo[i], g.lo[i + 1], dtype=np.int32)
        self.gpt, self.rmap = gpt, rmap
        self.mapped = (rmap.reshape(g.H, R) != FREE).sum(axis=1).astype(np.int32)
        self.bt = np.arange(g.H, dtype=np.int32)
        self.host_hist = np.zeros(g.H, np.uint8)
        self.region_epoch = np.full(g.H, -1, np.int32)
        self.epoch = 0
        if self.use_gpac:
            self.ipt_hist = np.zeros(g.N, np.uint8)
            self.counts = np.zeros(g.N, np.int32)
        self._gpt0 = gpt.copy() if not self.use_gpac else None

    # -- stateless part of a window (safe in worker threads) --------------
    def prepare(self, acc: np.ndarray) -> dict:
        """Global ids of one window's ``int32[n_guests, 1|, k]`` accesses;
        without GPAC the page tables never change, so the huge page of each
        access and the host counts are worked out here too."""
        g = self.geo
        acc = np.asarray(acc).reshape(g.n_guests, -1).astype(np.int64)
        ids = np.where(acc >= 0, acc + g.lo[:-1, None], -1)
        valid = (ids >= 0) & (ids < g.N)
        out = dict(valid=valid)
        if self.use_gpac:
            out["ids"] = ids
            out["uids"], out["ucnt"] = np.unique(ids[valid], return_counts=True)
        else:
            hp = np.where(valid, self._gpt0[np.where(valid, ids, 0)] // g.R, 0)
            out["hp"] = hp
            out["host_counts"] = np.bincount(hp[valid], minlength=g.H)
        return out

    # -- one window ------------------------------------------------------
    def step(self, pre: dict) -> dict:
        g = self.geo
        valid = pre["valid"]
        if self.use_gpac:
            ids = pre["ids"]
            hp = np.where(valid, self.gpt[np.where(valid, ids, 0)] // g.R, 0)
            host_counts = np.bincount(hp[valid], minlength=g.H)
        else:
            hp, host_counts = pre["hp"], pre["host_counts"]
        near = valid & (self.bt[hp] < g.n_near)
        near_hits = near.sum(axis=1)
        far_hits = (valid & ~near).sum(axis=1)
        moved = 0
        if self.use_gpac:
            uids, ucnt = pre["uids"], pre["ucnt"]
            self.counts[uids] = ucnt
            moved = self._gpac(uids)
        self._memtierd(host_counts)
        # roll
        self.host_hist = ((self.host_hist << 1) | (host_counts > 0)).astype(np.uint8)
        if self.use_gpac:
            self.ipt_hist <<= 1
            self.ipt_hist[uids] |= 1
            self.counts[uids] = 0
        self.epoch += 1
        in_near = (self.mapped > 0) & (self.bt < g.n_near)
        near_blocks = np.add.reduceat(in_near.astype(np.int64), g.hp_lo[:-1])
        return dict(near_hits=near_hits, far_hits=far_hits,
                    near_blocks=near_blocks, moved=moved)

    def _gpac(self, uids: np.ndarray) -> int:
        g, R = self.geo, self.geo.R
        mask = np.uint8((1 << min(g.ipt_windows, 8)) - 1)
        hits = _POP8[self.ipt_hist & mask]
        hits[uids] += 1
        hot = np.flatnonzero(hits >= g.ipt_min_hits)
        del hits
        hp_of = self.gpt[hot] // R
        per_hp = np.bincount(hp_of, minlength=g.H)
        cl = np.asarray(g.cl)[np.searchsorted(g.lo, hot, side="right") - 1]
        re = self.region_epoch[hp_of]
        cooling = (re >= 0) & (self.epoch - re < g.cooldown)
        cand = hot[(per_hp[hp_of] > 0) & (per_hp[hp_of] < cl) & ~cooling]
        score = self.counts[cand].astype(np.int64) * 256 + _POP8[self.ipt_hist[cand]]
        k = min(self.max_batches * R, max(g.pages))
        batches = np.full((g.n_guests, self.max_batches * R), -1, np.int64)
        bounds = np.searchsorted(cand, g.lo)
        for i in range(g.n_guests):
            c, s = cand[bounds[i]:bounds[i + 1]], score[bounds[i]:bounds[i + 1]]
            if self.tie == "high":
                c, s = c[::-1], s[::-1]
            top = c[np.argsort(-s, kind="stable")[:k]]
            batches[i, : top.size] = top
        batches = batches.reshape(g.n_guests, self.max_batches, R)
        moved = 0
        for b in range(self.max_batches):
            for i in range(g.n_guests):
                moved += self._consolidate(i, batches[i, b])
        return moved

    def _consolidate(self, i: int, pages: np.ndarray) -> int:
        """One Algorithm-1 call of guest i: ``pages`` (column = offset in
        the fresh region, -1 = hole) into its first fully free huge page."""
        g, R = self.geo, self.geo.R
        free = np.flatnonzero(self.mapped[g.hp_lo[i]:g.hp_lo[i + 1]] == 0)
        if free.size == 0:
            return 0
        region = int(g.hp_lo[i] + free[0])
        self.region_epoch[region] = self.epoch
        col = np.flatnonzero((pages >= 0) & (pages < g.N))
        if col.size == 0:
            return 0
        p = pages[col]
        old = self.gpt[p].astype(np.int64)
        new = region * R + col
        self.gpt[p] = new
        self.rmap[old] = FREE
        self.rmap[new] = p
        np.subtract.at(self.mapped, old // R, 1)
        self.mapped[region] += col.size
        return int(col.size)

    def _swap(self, far: np.ndarray, near: np.ndarray) -> None:
        s_far, s_near = self.bt[far].copy(), self.bt[near].copy()
        self.bt[far], self.bt[near] = s_near, s_far

    def _memtierd(self, host_counts: np.ndarray) -> None:
        g = self.geo
        score = host_counts.astype(np.int64) * 256 + _POP8[self.host_hist]
        alloc = self.mapped > 0
        in_near = self.bt < g.n_near
        budget = min(self.budget, g.H)
        victim = np.where(alloc, score, -(2**31) + 2)
        far = top_ids(alloc & ~in_near & (score > 0), score, budget, self.tie)
        near = top_ids(in_near, -victim, budget, self.tie)
        n = min(far.size, near.size)
        gain = score[far[:n]] > victim[near[:n]]
        n = int(np.cumprod(gain).sum()) if n else 0
        self._swap(far[:n], near[:n])
        in_near = self.bt < g.n_near
        zeros = np.zeros(g.H, np.int64)
        far = top_ids(~alloc & ~in_near, zeros, budget, self.tie)
        near = top_ids(alloc & in_near & (score == 0), zeros, budget, self.tie)
        n = min(far.size, near.size)
        self._swap(far[:n], near[:n])
