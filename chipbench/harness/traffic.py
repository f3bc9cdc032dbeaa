"""Traffic of a cell, made ahead of the engine.

A cell's mix (``traffic/<mix>.json``) names the generator that reads it
(``traffic/<generator>.py``); ``for_cell`` builds that generator for the
cell's fleet and seed. ``Feed`` makes windows ahead in a pool of threads
and hands them out in order.
"""
from __future__ import annotations

import collections
import concurrent.futures
import time

from harness import fleet, layout


def for_cell(cell: layout.Cell, seed: int, pool=None):
    """The cell's traffic from ``seed``: an object whose ``window(w)`` is
    ``int32[n_guests, 1, k]`` guest-local page ids (-1 padded), keyed on
    (seed, guest, absolute window)."""
    config = cell.config
    tenants = [t["workload"] for t in fleet.guests(config)]
    pages = [int(config["fleet"]["pages_per_guest"])] * len(tenants)
    return layout.generator_module(cell).make(cell.traffic, tenants, pages,
                                              seed, pool)


class Feed:
    """Windows ``start, start+1, ...`` of ``make(w)``, made ahead by a pool
    of threads and handed out in order. ``depth`` bounds how far ahead.
    ``get`` returns ``(w, item, seconds waited)``."""

    def __init__(self, make, start: int = 0, workers: int = 4, depth: int = 8):
        self._make = make
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="traffic")
        self._pending = collections.deque()
        self._next = start
        for _ in range(depth):
            self._submit()

    def _submit(self) -> None:
        self._pending.append(
            (self._next, self._pool.submit(self._make, self._next)))
        self._next += 1

    def get(self):
        w, fut = self._pending.popleft()
        t0 = time.perf_counter()
        item = fut.result()
        waited = time.perf_counter() - t0
        self._submit()
        return w, item, waited

    def close(self) -> None:
        for _, fut in self._pending:
            fut.cancel()
        self._pool.shutdown(wait=True)
        for _, fut in self._pending:
            if not fut.cancelled():
                fut.result()  # surface a generator error
        self._pending.clear()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
