#!/usr/bin/env python3
"""The control of a cell's comparison, and the faults it must catch.

    python3 benchmarks/chip/control.py --workload uln-l.serve-max \
        --seeds 1,2,3 --seconds 2

Serving cells: runs the cell's path at its own load for `--seconds`,
then reads each number its check compares for the program and for the
control: the plain reference with the AND across the k hashes dropped
(one hash read) put in the program's place, on the same requests.
Training cells: reads the compared numbers for the program over its
first steps (set-up and a window of one step), for the reference computed
with its table gathers in float8 (e4m3), the precision below the
configuration's bfloat16, and for the reference with half of each block's
rows left out, the mean taken over the rest.

Prints one JSON line per seed. The benchmark's own runs never run this:
it is how the limits in the traffic files were set, and a later change
to the comparison is held to it.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run


def serve_readings(cell) -> dict:
    from reference import wnn as reference
    served = run.load_module("paths", cell.traffic["path"]).setup(cell)
    win = served.run(cell.seconds)
    bits = served.pool[served.rows[np.arange(len(win.rids))
                                   % len(served.rows)]]
    tables = served.tables
    program = served.check(win)
    expect = reference.scores(tables, bits)
    control = reference.scores(tables, bits, hashes_used=1)
    return {"program": {k: v for k, (v, _) in program.items()},
            "control": {"wrong_answers": int(np.sum(np.any(
                control != expect, axis=1))), "compared": len(bits)}}


def train_readings(cell) -> dict:
    """The program's numbers over its first steps (a window of one step),
    and the control's and the half-batch fault's, all against one run of
    the reference."""
    import jax.numpy as jnp
    from paths import train_uleen as path
    from reference import uleen_train as reference
    trained = path.setup(cell)
    trained.run(0.0)
    t = cell.traffic
    args = dict(seed=trained.seed, steps=int(t["compared_steps"]),
                batch=int(t["global_batch"]),
                grad_blocks=int(t["grad_blocks"]))
    ref = reference.run(cell.config, trained.statics, trained.bits,
                        trained.labels, **args)
    none = {k: None for k in t["limits"]}

    def numbers(snap, losses):
        return {k: v for k, (v, _) in path.compare(snap, losses, ref,
                                                   none).items()}

    out = {"program": numbers(trained.snap, trained.losses)}
    for name, kw in (("control_float8", {"gather_dtype": jnp.float8_e4m3fn}),
                     ("half_batch", {"row_share": 0.5})):
        got = reference.run(cell.config, trained.statics, trained.bits,
                            trained.labels, **args, **kw)
        out[name] = numbers({"first": got["first"], "params": got["end"]},
                            got["losses"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    run.configure_jax()
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = run.load_cell(bench, args.workload, seed, args.seconds)
        read = (train_readings if cell.traffic["path"] == "train_uleen"
                else serve_readings)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **read(cell)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
