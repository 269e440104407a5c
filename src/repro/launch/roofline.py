"""Roofline-term extraction from compiled (AOT) artifacts.

Hardware peaks come from :data:`PEAKS`, keyed by the ``device_kind``
JAX reports; a device missing from the table is an error, never a
default.

* compute   = HLO_FLOPs_total   / (chips · peak)
* memory    = HLO_bytes_total   / (chips · HBM_bw)
* collective= collective_bytes  / (chips · link_bw)

``cost_analysis`` on the SPMD-partitioned module reports *per-device*
flops/bytes; totals are per-device × chips, so the two formulations agree.
``collective_bytes`` is not in ``cost_analysis``: we parse the optimized
HLO and sum operand bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute (including their
async -start forms).
"""
from __future__ import annotations

import dataclasses
import re

__all__ = [
    "PEAKS",
    "Hardware",
    "hardware_for",
    "collective_stats",
    "roofline_terms",
    "RooflineReport",
]


@dataclasses.dataclass(frozen=True)
class Hardware:
    peak_flops: float   # bf16 FLOP/s per chip
    hbm_bw: float       # B/s per chip
    ici_bw: float       # B/s per link


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s of chip-to-chip interconnect (~50 GB/s per link of four)
PEAKS = {
    "TPU v5 lite": Hardware(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def hardware_for(device_kind: str) -> Hardware:
    """The peaks of one chip kind; raises for a kind not in :data:`PEAKS`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}"
        ) from None


_COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_stats(hlo_text: str) -> dict:
    """Per-collective-kind operand-byte totals + op counts from HLO text."""
    totals = {k: 0 for k in _COLLECTIVE_OPS}
    counts = {k: 0 for k in _COLLECTIVE_OPS}
    largest = 0
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        kind = m.group(2)
        # operand shapes appear after the opcode's '('
        _, _, operands = line.partition(m.group(2))
        op_bytes = sum(
            _shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(operands)
        )
        totals[kind] += op_bytes
        counts[kind] += 1
        largest = max(largest, op_bytes)
    return {
        "bytes_by_kind": totals,
        "count_by_kind": counts,
        "total_bytes": sum(totals.values()),
        "largest_op_bytes": largest,
    }


@dataclasses.dataclass
class RooflineReport:
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    model_flops: float
    collectives: dict
    hw: Hardware
    xla_flops_per_device: float = 0.0   # raw cost_analysis (loop bodies ×1)
    xla_bytes_per_device: float = 0.0
    by_prim: dict = dataclasses.field(default_factory=dict)

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / self.hw.peak_flops

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / self.hw.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_device / self.hw.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline estimate: max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        total = self.flops_per_device * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """MODEL_FLOPs-based MFU at the roofline step time (the score)."""
        denom = self.step_time_s * self.chips * self.hw.peak_flops
        return self.model_flops / denom if denom else 0.0

    def to_dict(self) -> dict:
        return {
            "chips": self.chips,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "step_time_s": self.step_time_s,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "collectives": self.collectives,
            "xla_flops_per_device": self.xla_flops_per_device,
            "xla_bytes_per_device": self.xla_bytes_per_device,
            "by_prim": self.by_prim,
        }


def roofline_terms(
    compiled, chips: int, model_flops: float, hw: Hardware,
    walker_cost: dict | None = None,
) -> RooflineReport:
    """Build the report against ``hw`` (see :func:`hardware_for`).

    ``walker_cost`` (from :mod:`repro.launch.flops`) provides loop-aware
    GLOBAL flops/bytes; per-device = global / chips.  The raw
    ``cost_analysis`` numbers (per-device, loop bodies counted once) are
    kept for reference.  Collective bytes always come from the partitioned
    HLO (exact, per-device).
    """
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    if walker_cost is not None:
        flops = walker_cost["flops"] / chips
        byts = walker_cost["bytes"] / chips
        by_prim = walker_cost.get("by_prim", {})
    else:
        flops, byts, by_prim = xla_flops, xla_bytes, {}
    stats = collective_stats(compiled.as_text())
    return RooflineReport(
        chips=chips,
        flops_per_device=flops,
        bytes_per_device=byts,
        collective_bytes_per_device=float(stats["total_bytes"]),
        model_flops=model_flops,
        collectives=stats,
        hw=hw,
        xla_flops_per_device=xla_flops,
        xla_bytes_per_device=xla_bytes,
        by_prim=by_prim,
    )
