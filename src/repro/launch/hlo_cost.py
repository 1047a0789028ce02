"""Roofline terms from a compiled dry-run artifact.

    compute term    = HLO_FLOPs / (chips × peak)        [s]
    memory term     = HLO_bytes / (chips × HBM_bw)      [s]
    collective term = collective_bytes / (chips × link) [s]

`compiled.cost_analysis()` reports FLOPs/bytes of the *per-device* SPMD
module, and shapes in `compiled.as_text()` are per-device too, so the
chips factor cancels: each term is per-device-quantity / per-device-rate.

collective_bytes is not in cost_analysis: we parse the post-optimization
HLO, build a name → (bytes, shape) map from instruction definitions, and
sum *operand* bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (async start/done pairs counted once).
A ring-model link-traffic estimate (×2(g-1)/g for all-reduce, ×(g-1)/g
for gather/scatter, replica-group size g from the HLO) is reported
alongside the prescribed operand-bytes headline.

Hardware constants (TPU v5e-class target): 197 TFLOP/s bf16, 819 GB/s
HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

PEAK_FLOPS = 197e12      # bf16 / chip
HBM_BW = 819e9           # bytes/s / chip
ICI_BW = 50e9            # bytes/s / link

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[^)=]*?\)?)\s*"
                     r"([\w\-]+)\(")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute", "ragged-all-to-all")


def shape_bytes(type_str: str) -> float:
    """Bytes of an HLO type string, incl. tuple types '(f32[..], s8[..])'."""
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    name: str
    operand_bytes: float
    output_bytes: float
    group_size: int

    @property
    def link_bytes(self) -> float:
        """Ring-model per-device bytes over the wire."""
        g = max(2, self.group_size)
        if self.kind == "all-reduce":
            return self.operand_bytes * 2 * (g - 1) / g
        if self.kind == "all-gather":
            return self.output_bytes * (g - 1) / g
        if self.kind == "reduce-scatter":
            return self.operand_bytes * (g - 1) / g
        if self.kind in ("all-to-all", "ragged-all-to-all"):
            return self.operand_bytes * (g - 1) / g
        if self.kind == "collective-permute":
            return self.operand_bytes
        return self.operand_bytes


def parse_collectives(hlo_text: str) -> list:
    """All collective instructions with operand/output bytes + group size."""
    defs: dict = {}
    lines = hlo_text.splitlines()
    for line in lines:
        m = _DEF_RE.match(line)
        if m:
            name, type_str, _op = m.groups()
            defs[name] = shape_bytes(type_str)

    out = []
    for line in lines:
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, type_str, op = m.groups()
        base = op.replace("-start", "")
        if base not in COLLECTIVE_OPS or op.endswith("-done"):
            continue
        # operands: %names inside the call parens
        call = line[m.end():]
        call = call.split(", channel_id=")[0].split(", replica_groups=")[0]
        operand_bytes = 0.0
        for oname in _OPERAND_RE.findall(call):
            operand_bytes += defs.get(oname, 0.0)
        gm = _GROUPS_RE.search(line)
        if gm:
            group_size = int(gm.group(2))
        else:
            gl = _GROUPS_LIST_RE.search(line)
            group_size = len(gl.group(1).split(",")) if gl else 2
        out.append(CollectiveOp(kind=base, name=name,
                                operand_bytes=operand_bytes,
                                output_bytes=shape_bytes(type_str),
                                group_size=group_size))
    return out


@dataclasses.dataclass
class Roofline:
    flops_per_device: float          # trip-expanded dot FLOPs (per device)
    hbm_bytes_per_device: float      # trip-expanded operand+output bytes
    collective_bytes_per_device: float   # operand bytes (the prescription)
    link_bytes_per_device: float     # ring-model wire bytes
    collectives_by_kind: dict
    xla_flops_raw: float             # cost_analysis (loop bodies once)
    xla_bytes_raw: float
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    useful_ratio: float              # MODEL_FLOPS / (flops × chips)
    dominant: str

    def summary(self) -> dict:
        return dataclasses.asdict(self)


def roofline_from(compiled_text: str, cost: dict, chips: int,
                  model_flops: float) -> Roofline:
    """Trip-count-aware roofline (see hlo_analysis): XLA's cost_analysis
    counts while bodies once, so the headline terms come from the expanded
    walk; the raw XLA numbers are kept for reference."""
    from repro.launch import hlo_analysis
    mod = hlo_analysis.analyze(compiled_text)

    flops = mod.dot_flops
    hbm = mod.hbm_bytes
    op_bytes = mod.collective_operand_bytes
    compute_s = flops / PEAK_FLOPS
    memory_s = hbm / HBM_BW
    collective_s = op_bytes / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    total_flops = flops * chips
    return Roofline(
        flops_per_device=flops, hbm_bytes_per_device=hbm,
        collective_bytes_per_device=op_bytes,
        link_bytes_per_device=mod.collective_link_bytes,
        collectives_by_kind=mod.by_kind(),
        xla_flops_raw=float(cost.get("flops", 0.0)),
        xla_bytes_raw=float(cost.get("bytes accessed", 0.0)),
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        model_flops=model_flops,
        useful_ratio=model_flops / total_flops if total_flops else 0.0,
        dominant=dominant)


def model_flops_for(cfg, shape) -> float:
    """6·N·D (train), 2·N·D (prefill), 2·N_active·B (decode)."""
    n_act = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_act * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_act * shape.global_batch * shape.seq_len
    return 2.0 * n_act * shape.global_batch            # decode: 1 token
