"""The comparison that decides `correct`, shown to fail.

The control: the plain reference with the AND across the k hashes
dropped (one hash read), in the program's place. And the whole run of
each cell, off the chip and at a size a test run holds, with the timed
path broken underneath: for serving, an answer altered where it is
produced and every other row of each launch left unscored; for training,
a state left unchanged, half the gradient blocks folded and a loss
altered. Each must read `correct` false; the unbroken run must read it
true.
"""
import numpy as np
import pytest

import generators
import run
from reference import wnn as reference

CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SEEDS = (3, 2 ** 31 + 17, 90210)


def small_cell(bench, workload, seed):
    cell = run.load_cell(bench, workload, seed, 0.3)
    cell.traffic = dict(cell.traffic, pool_rows=256, warmup_s=0.0,
                        slots=128, backlog=256)
    return cell


@pytest.mark.parametrize("seed", SEEDS)
def test_control_differs_from_reference(bench, seed):
    cell = run.load_cell(bench, "uln-l.serve-max", seed, 1.0)
    tables = generators.tables(cell.config, seed)
    bits, _ = generators.encoded_digits(seed, 64, cell.config)
    expect = reference.scores(tables, bits)
    control = reference.scores(tables, bits, hashes_used=1)
    wrong = int(np.sum(np.any(control != expect, axis=1)))
    assert wrong == len(bits)


def broken(cell, fault):
    """The cell's path module with `fault` planted under it."""
    real = run.load_module("paths", cell.traffic["path"])

    class Path:
        @staticmethod
        def setup(c):
            served = real.setup(c)
            scores = served.batcher._scores
            if fault == "answer_altered":
                served.batcher._scores = (
                    lambda *a: scores(*a).at[0, 0].add(1))
            elif fault == "half_batch":
                served.batcher._scores = (
                    lambda *a: scores(*a).at[1::2].set(0))
            return served
    return Path


@pytest.mark.parametrize("fault", ["none", "answer_altered", "half_batch"])
def test_broken_timed_path_is_not_correct(bench, fault):
    cell = small_cell(bench, "uln-l.serve-max", 2 ** 31 + 5)
    res = run.run_cell(bench, cell, trace=False, device=CPU, t_start=0.0,
                       path=broken(cell, fault))
    assert res["attempted"] > 0
    assert res["correct"] is (fault == "none"), res["checks"]
    assert list(res)[-1] == "checks"


def tiny_train_cell(bench, seed):
    """The training cell at a size the CPU holds: two submodels over 256
    input bits, batch 64 in the cell's 8 gradient blocks."""
    cell = run.load_cell(bench, "uln-l.train", seed, 1.0)
    cell.config = dict(cell.config, bits_per_input=2, total_bits=256,
                       submodels=cell.config["submodels"][:2])
    cell.traffic = dict(cell.traffic, train_rows=256, global_batch=64)
    return cell


def plant(fault, monkeypatch):
    """`fault` planted in the program's trainer."""
    import jax
    from repro.core import multi_shot
    if fault == "state_unchanged":
        monkeypatch.setattr(multi_shot, "apply_step",
                            lambda opt, p, s, g, clip=1.0: (p, s))
    elif fault == "half_batch":
        fold = multi_shot.fold_blocks

        def half(grads, losses, accs):
            h = losses.shape[0] // 2
            return fold(jax.tree.map(lambda x: x[:h], grads), losses[:h],
                        accs[:h])
        monkeypatch.setattr(multi_shot, "fold_blocks", half)
    elif fault == "answer_altered":
        ce = multi_shot.cross_entropy
        monkeypatch.setattr(multi_shot, "cross_entropy",
                            lambda *a, **k: ce(*a, **k) * 1.01)


@pytest.mark.parametrize("fault", ["none", "state_unchanged", "half_batch",
                                   "answer_altered"])
def test_broken_train_step_is_not_correct(bench, fault, monkeypatch):
    cell = tiny_train_cell(bench, 2 ** 31 + 11)
    plant(fault, monkeypatch)
    res = run.run_cell(bench, cell, trace=False, device=CPU, t_start=0.0)
    assert res["attempted"] > 0
    assert res["correct"] is (fault == "none"), res["checks"]
