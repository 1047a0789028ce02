"""One ULEEN artifact served through `launch/scheduler.py::WnnBatcher`.

The traffic file gives `slots`, the `backlog` the window's loop keeps
queued and the input pool; the batcher picks its scoring backend as it
would in production (`auto`: the packed Pallas kernel on a TPU). The
check compares every answer of the window with the plain reference.
"""
from __future__ import annotations

import gc

import numpy as np

import drive
import generators
from reference import wnn as reference


def artifact(config: dict, tables: dict):
    """`tables` as the program's deployable artifact."""
    from repro.core import export
    subs = [export.SubmodelArtifact(
        packed=s["words"], mask=s["mask"], perm=s["perm"], h3=s["h3"],
        entries=s["entries"], inputs_per_filter=s["inputs_per_filter"],
        num_hashes=s["num_hashes"]) for s in tables["submodels"]]
    return export.InferenceArtifact(
        submodels=subs, bias=tables["bias"],
        num_classes=config["num_classes"], total_bits=config["total_bits"],
        bits_per_input=config["bits_per_input"])


class Served:
    """The batcher under the window's loop, and what its check needs."""

    def __init__(self, cell):
        from repro.launch.scheduler import WnnBatcher
        traffic = cell.traffic
        self.cell = cell
        self.tables = generators.tables(cell.config, cell.seed)
        self.batcher = WnnBatcher(artifact(cell.config, self.tables),
                                  slots=int(traffic["slots"]))
        self.pool, _ = generators.encoded_digits(
            cell.seed, int(traffic["pool_rows"]), cell.config)
        self.rows = generators.request_rows(traffic, cell.seed)
        self._results = self.batcher.results

    # the server interface of `drive`
    def submit(self, i: int) -> int:
        return self.batcher.submit(self.pool[self.rows[i % len(self.rows)]])

    def step(self) -> int:
        return self.batcher.step()

    def queued(self) -> int:
        return len(self.batcher.queue)

    def done_at(self, rid: int) -> float:
        t = self._results[rid].t_done
        return np.inf if t is None else t

    def warm(self, seconds: float) -> None:
        """Compile the one batch program with a full launch, then run the
        cell's own traffic for `seconds`."""
        for i in range(self.batcher.slots):
            self.submit(i)
        self.step()
        if seconds > 0:
            self.run(seconds)

    def run(self, seconds: float, *, traced: bool = False,
            begin=None) -> drive.Window:
        return drive.backlog(self, int(self.cell.traffic["backlog"]),
                             seconds, traced=traced, begin=begin)

    def check(self, win: drive.Window) -> dict:
        """Free the program, then compare every answer of the window with
        the plain reference: every class score must be equal. The
        reference scores the input pool once."""
        m = self.cell.config["num_classes"]
        got = np.full((len(win.rids), m), np.iinfo(np.int32).min, np.int32)
        unanswered = 0
        for i, rid in enumerate(win.rids):
            s = self._results[int(rid)].scores
            if s is None:
                unanswered += 1
            else:
                got[i] = s
        self.batcher = self._results = None
        gc.collect()
        expect = reference.scores(self.tables, self.pool)[
            self.rows[np.arange(len(win.rids)) % len(self.rows)]]
        wrong = int(np.sum(np.any(got != expect, axis=1)))
        return {"wrong_answers": (wrong, 0), "unanswered": (unanswered, 0),
                "compared": (len(win.rids), None)}


def setup(cell) -> Served:
    served = Served(cell)
    served.warm(float(cell.traffic["warmup_s"]))
    return served
