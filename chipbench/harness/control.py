"""The control: the reference with one stated guarantee broken, put in the
program's place, must come out not correct.

The configurations state that decisions are exact, ties going to the lowest
page or huge-page id (the order the program's tie-exact top-k rebuilds on
the chip at a cost; XLA's own top_k there does not keep it). The control
breaks ties towards the highest id instead: the step a faster, inexact
top-k would take. It drives the same windows from the same seed as a run,
and the check compares it with the reference as it compares the program.
"""
from __future__ import annotations

import numpy as np

from harness import check, layout, traffic as traffic_mod
from harness.traffic import Feed

WORKERS = 8


class Control:
    """The reference, ties broken the other way, as a system under test."""

    def __init__(self, cell: layout.Cell, seed: int):
        ref_mod = layout.reference_module(cell)
        self.ref = ref_mod.Fleet(cell.config["fleet"], cell.config["policy"],
                                 tie="high")
        self.seed = seed

    def step(self, acc: np.ndarray) -> dict:
        r = self.ref.step(self.ref.prepare(acc))
        return {k: r[k] for k in check.SERIES}

    def final(self) -> dict:
        return dict(gpt=self.ref.gpt.copy(), block_table=self.ref.bt.copy(),
                    seed=self.seed)


def readings(cell: layout.Cell, seed: int, n_windows: int) -> dict:
    """The numbers the check compares, for the control over windows
    0..n_windows-1 of ``seed``."""
    config = cell.config
    traffic = traffic_mod.for_cell(cell, seed)
    control = Control(cell, seed)
    got = {k: [] for k in check.SERIES}
    with Feed(traffic.window, 0, WORKERS, depth=2 * WORKERS) as feed:
        for _ in range(n_windows):
            _, acc, _ = feed.get()
            for k, v in control.step(acc).items():
                got[k].append(np.asarray(v))
    final = control.final()
    ref = layout.reference_module(cell).Fleet(config["fleet"], config["policy"])
    want, _ = check.replay(ref, traffic, n_windows, WORKERS)
    got = {k: np.stack(v) for k, v in got.items()}
    return check.compare(config, got, want, ref, final)
