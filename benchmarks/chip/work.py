"""Work counted from the geometry, and the chips' published peaks.

Every roofline and `mfu` share of the benchmark divides the least time
that this work could take at the chip's peaks by a measured time. The
work is counted from the ULEEN geometry alone (B rows, and per submodel
N_f filters of n inputs, E entries, k hashes, over M classes), never from
the arrays' dtypes or layout, so that a later change to how the work is
done (packed input bits, a fused permutation gather) leaves it as it is.

Serving, per submodel and launch of B rows:
- bytes: input tuples at 1 bit per bit (B * N_f * n / 8), the tables at
  1 bit per entry (M * N_f * E / 8), the partial scores at 4 bytes
  (B * M * 4);
- operations: H3 (one select-XOR per input bit and hash, B * N_f * k * n),
  lookups (B * M * N_f * k), AND across k (B * M * N_f * (k - 1)),
  popcount (one add per filter, B * M * N_f).

Training, per step of B rows:
- operations: the forward lookups, the min across k and the sum per
  class as in serving, the backward scatter-adds (one per row, class and
  filter, routed through the min to one entry), and the Adam update at
  ADAM_OPS_PER_ENTRY operations over every table entry;
- bytes: the input bits at 1 bit per bit, the labels at 4 bytes, and the
  Adam update reading and writing the table, its first and its second
  moment in float32 (24 bytes per entry).
"""
from __future__ import annotations

import dataclasses
import math

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 819e9},
}

# m and v moments, their bias corrections, the square root, the division,
# the step and the clip to [-1, 1]: ten operations per table entry.
ADAM_OPS_PER_ENTRY = 10
ADAM_BYTES_PER_ENTRY = 3 * 2 * 4


def peaks(device_kind: str) -> dict:
    """The peak table's row for `device_kind`; an unknown chip raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


@dataclasses.dataclass(frozen=True)
class Work:
    ops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.ops + other.ops, self.bytes + other.bytes)

    def least_seconds(self, peak_ops: float, peak_bytes: float):
        """(least seconds at the peaks, "ops" or "bytes": the bound)."""
        t_ops, t_bytes = self.ops / peak_ops, self.bytes / peak_bytes
        return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")


def _geometry(config: dict):
    m, total = config["num_classes"], config["total_bits"]
    for sm in config["submodels"]:
        n, k = sm["inputs_per_filter"], sm["num_hashes"]
        yield m, math.ceil(total / n), n, 2 ** sm["log2_entries"], k


def table_entries(config: dict) -> int:
    """M * N_f * E summed over submodels."""
    return sum(m * n_f * e for m, n_f, _, e, _ in _geometry(config))


def serve_submodel(b: int, m: int, n_f: int, n: int, e: int,
                   k: int) -> Work:
    """One launch of the lookup kernel over B rows of one submodel."""
    ops = b * n_f * k * n + b * m * n_f * (k + (k - 1) + 1)
    by = b * n_f * n / 8 + m * n_f * e / 8 + b * m * 4
    return Work(ops, by)


def serve_launch(config: dict, b: int) -> Work:
    """One scoring launch of B rows through every submodel."""
    out = Work(0.0, 0.0)
    for geo in _geometry(config):
        out = out + serve_submodel(b, *geo)
    return out


def train_step(config: dict, b: int) -> Work:
    """One optimizer step of B rows."""
    ops = 0.0
    for m, n_f, _, _, k in _geometry(config):
        ops += b * m * n_f * (k + (k - 1) + 1 + 1)
    entries = table_entries(config)
    ops += ADAM_OPS_PER_ENTRY * entries
    by = b * config["total_bits"] / 8 + b * 4 + ADAM_BYTES_PER_ENTRY * entries
    return Work(ops, by)
