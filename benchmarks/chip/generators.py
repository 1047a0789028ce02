"""Seeded inputs, artifacts and arrivals of the chip benchmark.

Everything here is NumPy on the host and a pure function of its seed, so
the same seed gives the same inputs in every run, and a later change to
the program cannot move what the benchmark feeds it.

- `digits`: synthetic 28x28 digit-like images (smooth class prototypes,
  two style components, pixel noise, +-1 px shifts), in place of MNIST.
- `Thermometer`: the Gaussian thermometer of ULEEN Sec. III-A2: per-pixel
  thresholds at the quantiles i/(t+1) of a normal fitted to the pixel.
- `tables`: seeded deployable tables at a geometry: random H3
  parameters and input orders, bitplanes a quarter set, 90% of filters
  kept.
- `request_rows`: the input row of each request a traffic file sends.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


def rng_of(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream...); any integer seed."""
    return np.random.default_rng([int(seed) % 2 ** 63, *stream])


def _bilinear(coarse: np.ndarray, hw: int) -> np.ndarray:
    """(..., c, c) -> (..., hw, hw), half-pixel-centred bilinear resize."""
    c = coarse.shape[-1]
    pos = np.clip((np.arange(hw) + 0.5) * c / hw - 0.5, 0, c - 1)
    lo = np.floor(pos).astype(int)
    hi = np.minimum(lo + 1, c - 1)
    w = pos - lo
    rows = (coarse[..., lo, :] * (1 - w)[:, None]
            + coarse[..., hi, :] * w[:, None])
    return rows[..., lo] * (1 - w) + rows[..., hi] * w


def digits(seed: int, n: int, *, hw: int = 28, classes: int = 10,
           noise: float = 0.45, warp: float = 0.35):
    """(images (n, hw*hw) float32 in (0, 1), labels (n,) int32).

    The class prototypes are fixed by `seed`; every image differs."""
    rng = rng_of(seed, 1)
    protos = _bilinear(rng.standard_normal((classes, 4, 4)), hw)
    styles = _bilinear(rng.standard_normal((classes, 2, 4, 4)), hw)
    labels = rng.integers(0, classes, n).astype(np.int32)
    mix = rng.standard_normal((n, 2)) * warp
    img = (protos[labels] + np.einsum("ns,nsij->nij", mix, styles[labels])
           + noise * rng.standard_normal((n, hw, hw)))
    shifts = rng.integers(-1, 2, (n, 2))
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            sel = (shifts[:, 0] == dy) & (shifts[:, 1] == dx)
            img[sel] = np.roll(img[sel], (dy, dx), axis=(1, 2))
    img = 1.0 / (1.0 + np.exp(-2.0 * img))
    return img.reshape(n, hw * hw).astype(np.float32), labels


@dataclasses.dataclass(frozen=True)
class Thermometer:
    thresholds: np.ndarray          # (features, bits)

    @classmethod
    def fit(cls, x: np.ndarray, bits: int) -> "Thermometer":
        z = np.array([statistics.NormalDist().inv_cdf(i / (bits + 1))
                      for i in range(1, bits + 1)])
        mean = x.mean(0)
        std = x.std(0) + 1e-6
        return cls((mean[:, None] + std[:, None] * z[None]).astype(
            np.float32))

    def encode(self, x: np.ndarray) -> np.ndarray:
        """(n, F) -> (n, F*T) uint8 {0,1}: feature f owns bits
        [f*T, (f+1)*T), unary from the lowest threshold up."""
        bits = x[:, :, None] > self.thresholds[None]
        return bits.reshape(x.shape[0], -1).astype(np.uint8)


def encoded_digits(seed: int, n: int, config: dict):
    """(bits (n, total_bits) uint8, labels) under the config's encoding,
    the thermometer fitted on the same images."""
    x, y = digits(seed, n)
    enc = Thermometer.fit(x, config["bits_per_input"])
    return enc.encode(x), y


def num_filters(config: dict, sm: dict) -> int:
    return math.ceil(config["total_bits"] / sm["inputs_per_filter"])


def tables(config: dict, seed: int) -> dict:
    """Seeded deployable tables of the config's geometry, per submodel:
    words (M, N_f, W) uint32, mask (M, N_f) bool, perm (N_f, n) int32,
    h3 (k, n) uint32; and bias (M,) int32."""
    rng = rng_of(seed, 2)
    m, total = config["num_classes"], config["total_bits"]
    subs = []
    for sm in config["submodels"]:
        n, k = sm["inputs_per_filter"], sm["num_hashes"]
        entries = 2 ** sm["log2_entries"]
        n_f = num_filters(config, sm)
        shape = (m, n_f, max(1, entries // 32))
        words = (rng.integers(0, 2 ** 32, shape, dtype=np.uint32)
                 & rng.integers(0, 2 ** 32, shape, dtype=np.uint32))
        order = rng.permutation(total).astype(np.int32)
        extra = n_f * n - total
        if extra > 0:       # wrap: re-sample the missing inputs
            order = np.concatenate(
                [order, rng.integers(0, total, extra, dtype=np.int32)])
        subs.append({
            "words": words,
            "mask": rng.random(shape[:2]) < 0.9,
            "perm": order[:n_f * n].reshape(n_f, n),
            "h3": rng.integers(0, entries, (k, n), dtype=np.uint32),
            "entries": entries, "inputs_per_filter": n, "num_hashes": k})
    return {"submodels": subs,
            "bias": rng.integers(-4, 5, m).astype(np.int32)}


def request_rows(traffic: dict, seed: int) -> np.ndarray:
    """The input-pool row of each request, in the order they are sent:
    64 times the backlog the loop keeps queued, then round again."""
    return rng_of(seed, 3).integers(0, int(traffic["pool_rows"]),
                                    int(traffic["backlog"]) * 64)
