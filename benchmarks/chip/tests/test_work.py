"""Work counted from the geometry, pinned against hand-computed values."""
import json
from pathlib import Path

import pytest

import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


# ULN-S of arXiv:2304.10618 Table I: 784 px x 2 bits, (n, E) = (12, 64),
# (16, 64), (20, 64), k = 2
ULN_S = {"num_classes": 10, "total_bits": 1568,
         "submodels": [{"inputs_per_filter": n, "log2_entries": 6,
                        "num_hashes": 2} for n in (12, 16, 20)]}


def test_uln_s_serve_counts():
    # N_f = 131, 98, 79 (ceil of 1568 / n); E = 64; k = 2; M = 10
    cfg = ULN_S
    assert work.table_entries(cfg) == 10 * 64 * (131 + 98 + 79) == 197120
    one = work.serve_launch(cfg, 1)
    h3 = 131 * 2 * 12 + 98 * 2 * 16 + 79 * 2 * 20            # 9440
    lookup_and_popcount = 10 * 308 * (2 + 1 + 1)              # 12320
    assert one.ops == h3 + lookup_and_popcount == 21760
    tuples = (131 * 12 + 98 * 16 + 79 * 20) / 8               # 590 bytes
    assert one.bytes == tuples + 197120 / 8 + 3 * 10 * 4 == 25350


def test_uln_l_serve_counts():
    # N_f = 458, 343, 275, 229, 196, 172: 1,673 filters per class
    cfg = config("uln-l")
    assert work.table_entries(cfg) == 3052800
    launch = work.serve_launch(cfg, 1024)
    assert launch.ops == 1024 * 2 * 32972 + 1024 * 10 * 1673 * 4 \
        == 136052736
    assert launch.bytes == 1024 * 32972 / 8 + 3052800 / 8 \
        + 6 * 1024 * 10 * 4 == 4847776
    least, bound = launch.least_seconds(**_v5e())
    assert bound == "bytes"
    assert least == pytest.approx(4847776 / 819e9)


def test_uln_l_train_counts():
    cfg = config("uln-l")
    step = work.train_step(cfg, 1024)
    assert step.ops == 1024 * 10 * 1673 * 5 + 10 * 3052800 == 116185600
    assert step.bytes == 1024 * 5488 / 8 + 1024 * 4 + 24 * 3052800 \
        == 73973760


def _v5e():
    p = work.peaks("TPU v5 lite")
    return {"peak_ops": p["int8_ops"], "peak_bytes": p["hbm_bytes"]}


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks("TPU v4")
    with pytest.raises(KeyError):
        work.peaks("cpu")
