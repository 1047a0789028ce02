"""The seeded generators: the same seed gives the same inputs, every
seed the same sizes, and the traffic file's parameters hold."""
import numpy as np

import generators

CFG = {"num_classes": 10, "total_bits": 1568, "bits_per_input": 2,
       "submodels": [{"inputs_per_filter": 12, "log2_entries": 6,
                      "num_hashes": 2},
                     {"inputs_per_filter": 20, "log2_entries": 6,
                      "num_hashes": 2}]}


def test_same_seed_same_inputs_any_seed_size():
    a, ya = generators.encoded_digits(2 ** 31 + 3, 64, CFG)
    b, yb = generators.encoded_digits(2 ** 31 + 3, 64, CFG)
    c, _ = generators.encoded_digits(7, 64, CFG)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(ya, yb)
    assert a.shape == c.shape == (64, 1568) and a.dtype == np.uint8
    assert not np.array_equal(a, c)
    # every pixel's 2-bit thermometer code is unary: bit 1 implies bit 0
    pix = a.reshape(64, 784, 2)
    assert np.all(pix[..., 1] <= pix[..., 0])


def test_tables_geometry_and_density():
    t = generators.tables(CFG, 5)
    s0, s1 = t["submodels"]
    assert s0["words"].shape == (10, 131, 2)          # ceil(1568 / 12)
    assert s1["words"].shape == (10, 79, 2)           # ceil(1568 / 20)
    assert s0["perm"].shape == (131, 12)
    assert s0["h3"].shape == (2, 12) and s0["h3"].max() < 64
    # the input order covers every input bit
    assert set(s0["perm"].reshape(-1)) == set(range(1568))
    bits = np.unpackbits(s0["words"].view(np.uint8))
    assert 0.2 < bits.mean() < 0.3                   # a quarter set
    assert 0.85 < s0["mask"].mean() < 0.95            # 90% kept
    assert t["bias"].shape == (10,)
    again = generators.tables(CFG, 5)
    np.testing.assert_array_equal(s0["words"], again["submodels"][0]["words"])


def test_request_rows_from_the_traffic_file():
    traffic = {"backlog": 8, "pool_rows": 5}
    rows = generators.request_rows(traffic, 2 ** 31 + 1)
    assert len(rows) == 8 * 64 and rows.min() >= 0 and rows.max() < 5
    np.testing.assert_array_equal(
        rows, generators.request_rows(traffic, 2 ** 31 + 1))
