"""ULEEN multi-shot STE training through `launch/train.py::train_uleen`.

One call of the trainer carries the whole run, on a one-chip `("data",)`
mesh. Its first `warm_steps` steps, the first of which compiles, are
set-up; the window then times the steps that follow, through the same
call and feed, and closes when the first step that ends `--seconds` or
more after it opened ends: the window is a whole number of steps, so its
rate is not rounded to one. The trainer is then asked to stop through its
preemption guard. The traffic file gives the global batch, the gradient
blocks and the training rows, all drawn from the seed.

The check follows the first `compared_steps` steps with the plain
reference (`reference/uleen_train.py`): each step's loss, the change of
the parameters over the first step (the first gradient as Adam applied
it: the trainer hands its step hook the parameters, not the optimizer
state), and their change over all the compared steps. Norms are compared
leaf by leaf, the gap of the two norms measured against the larger of the
reference's norm of that leaf and of the median leaf. Leaves whose
reference gradient is under a thousandth of the median leaf's (the
pruning masks, which no gradient reaches) are left out.
"""
from __future__ import annotations

import gc

import numpy as np

import drive
import generators


def spec_of(config: dict):
    """The configuration as the program's `UleenSpec`."""
    from repro.core.model import SubmodelSpec, UleenSpec
    tr = config["training"]
    return UleenSpec(
        num_classes=config["num_classes"], total_bits=config["total_bits"],
        submodels=tuple(SubmodelSpec(sm["inputs_per_filter"],
                                     sm["log2_entries"], sm["num_hashes"])
                        for sm in config["submodels"]),
        bits_per_input=config["bits_per_input"], dropout=tr["dropout"],
        dropout_shared_classes=tr["dropout_shared_classes"],
        bf16_tables=tr["gather_dtype"] == "bfloat16")


def program_seed(seed: int) -> int:
    """The trainer takes a 32-bit seed."""
    return int(seed) % (2 ** 31 - 1)


class Trained:
    def __init__(self, cell):
        import jax.numpy as jnp
        from repro.core.model import SubmodelStatic
        self.cell = cell
        t = cell.traffic
        self.seed = program_seed(cell.seed)
        self.bits, self.labels = generators.encoded_digits(
            cell.seed, int(t["train_rows"]), cell.config)
        tables = generators.tables(cell.config, cell.seed)
        self.statics = [(s["perm"], s["h3"])
                        for s in tables["submodels"]]
        self.spec = spec_of(cell.config)
        self.program_statics = [
            SubmodelStatic(perm=jnp.asarray(p), h3=jnp.asarray(h))
            for p, h in self.statics]
        self.snap = {}

    def run(self, seconds: float, *, traced: bool = False,
            begin=None) -> drive.Window:
        import jax
        from repro.launch import train as train_mod
        from repro.launch.mesh import make_mesh
        from repro.train.fault import PreemptionGuard
        t = self.cell.traffic
        batch = int(t["global_batch"])
        warm = int(t["warm_steps"])
        compared = int(t["compared_steps"])
        guard = PreemptionGuard()
        note = drive.annotator(traced)
        win = {"steps": [], "t0": None}

        def on_step(step, params):
            now = drive.clock()
            if step == 0:
                self.snap["first"] = jax.tree.map(np.asarray, params)
            if step == compared - 1:
                self.snap["params"] = jax.tree.map(np.asarray, params)
            if step == warm - 1:
                if begin is not None:
                    begin()
                win["note"] = note("bench.window")   # made once tracing is on
                win["note"].__enter__()
                win["t0"] = win["last"] = drive.clock()
                return
            if win["t0"] is None:
                return
            win["steps"].append((win["last"], now, batch))
            win["last"] = now
            if now >= win["t0"] + seconds:
                win["note"].__exit__(None, None, None)
                guard.request()

        out = train_mod.train_uleen(
            self.spec, self.program_statics, self.bits, self.labels,
            steps_total=10 ** 9, global_batch=batch,
            grad_blocks=int(t["grad_blocks"]), seed=self.seed,
            mesh=make_mesh((1,), ("data",)), guard=guard,
            on_step=on_step, verbose=False)
        self.losses = [h["loss"] for h in out["history"][:compared]]
        del out
        steps = win["steps"]
        return drive.Window(
            t0=win["t0"], t1=steps[-1][1], rids=np.arange(len(steps)),
            submitted=np.array([s[0] for s in steps]),
            done=np.array([s[1] for s in steps]),
            steps=steps, examples=batch * len(steps))

    def check(self, win) -> dict:
        from reference import uleen_train as reference
        t = self.cell.traffic
        gc.collect()
        ref = reference.run(
            self.cell.config, self.statics, self.bits, self.labels,
            seed=self.seed, steps=int(t["compared_steps"]),
            batch=int(t["global_batch"]), grad_blocks=int(t["grad_blocks"]))
        return compare(self.snap, self.losses, ref, t["limits"])


def _leaves(tree) -> dict:
    """{name: array} of a parameter tree given as the program's
    `UleenParams` or as the reference's dict."""
    if not isinstance(tree, dict):
        tree = tree._asdict()
    out = {f"tables.{i}": x for i, x in enumerate(tree["tables"])}
    out.update({f"masks.{i}": x for i, x in enumerate(tree["masks"])})
    out["bias"] = tree["bias"]
    return out


def norm_gap(got: dict, want: dict, keep) -> float:
    """Worst leaf's |norm(got) - norm(want)| over the larger of
    norm(want) and the median leaf's norm of `want`."""
    norms = {k: float(np.linalg.norm(np.asarray(v, np.float64)))
             for k, v in want.items() if k in keep}
    median = float(np.median(list(norms.values())))
    worst = 0.0
    for k in keep:
        g = float(np.linalg.norm(np.asarray(got[k], np.float64)))
        worst = max(worst, abs(g - norms[k]) / max(norms[k], median))
    return worst


def compare(snap: dict, losses: list, ref: dict, limits: dict) -> dict:
    """The compared numbers of a training run against the reference, each
    with its limit from the traffic file."""
    gnorm = {k: float(np.linalg.norm(v))
             for k, v in _leaves(ref["first_grad"]).items()}
    median = float(np.median(list(gnorm.values())))
    keep = [k for k, v in gnorm.items() if v >= 1e-3 * median]
    want_loss = np.asarray(ref["losses"])
    got_loss = np.asarray(losses + [np.nan] * (len(want_loss) - len(losses)))
    loss_gap = float(np.max(np.abs(got_loss - want_loss) / np.abs(want_loss)))
    start = _leaves(ref["start"])

    def change(tree):
        return {k: v - start[k] for k, v in _leaves(tree).items()}

    first_gap = norm_gap(change(snap["first"]), change(ref["first"]), keep)
    change_gap = norm_gap(change(snap["params"]), change(ref["end"]), keep)
    return {"loss_gap": (loss_gap, limits["loss_gap"]),
            "first_change_gap": (first_gap, limits["first_change_gap"]),
            "change_norm_gap": (change_gap, limits["change_norm_gap"])}


def setup(cell) -> Trained:
    return Trained(cell)
