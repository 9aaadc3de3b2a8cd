"""Roofline-term extraction from compiled XLA artifacts.

Sources (per §Roofline):
- ``compiled.cost_analysis()``  -> per-device HLO FLOPs and bytes accessed
- ``compiled.as_text()``        -> post-SPMD HLO; collective bytes are summed
  from the operand/output sizes of every all-gather / all-reduce /
  reduce-scatter / all-to-all / collective-permute

Hardware model: the published per-chip peaks in ``PEAKS``, keyed by the
``device_kind`` jax reports.  Effective wire bytes per collective use the
standard ring-algorithm factors with the participant count parsed from
replica_groups.
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip
    ici_bw: float  # ICI bytes/s per link


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s of chip-to-chip interconnect (4 links, so 50 GB/s each)
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks_for(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}"
        ) from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(sig: str) -> int:
    """Total bytes of all array shapes in an HLO type signature string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(sig):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))  # [n_groups, group_size]<=[total]
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


@dataclasses.dataclass
class CollectiveStats:
    counts: dict[str, int]
    raw_bytes: dict[str, float]  # per-device output bytes by op kind
    wire_bytes: float  # ring-model effective bytes over the ICI link

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def parse_collectives(hlo_text: str, default_group: int = 2) -> CollectiveStats:
    counts: dict[str, int] = {}
    raw: dict[str, float] = {}
    wire = 0.0
    for line in hlo_text.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        kind = None
        for c in _COLLECTIVES:
            # match "  %x = TYPE all-gather(" or fused variants like all-gather-start
            if re.search(rf"\s{c}(-start)?\(", s):
                kind = c
                break
        if kind is None:
            continue
        lhs = s.split("=", 1)[1]
        out_bytes = _shape_bytes(lhs.split("(", 1)[0])
        n = max(_group_size(s, default_group), 2)
        counts[kind] = counts.get(kind, 0) + 1
        raw[kind] = raw.get(kind, 0.0) + out_bytes
        if kind == "all-reduce":
            wire += 2.0 * (n - 1) / n * out_bytes
        elif kind in ("all-gather", "reduce-scatter", "all-to-all"):
            wire += (n - 1) / n * out_bytes
        else:  # collective-permute
            wire += out_bytes
    return CollectiveStats(counts=counts, raw_bytes=raw, wire_bytes=wire)


@dataclasses.dataclass
class RooflineTerms:
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float  # 6*N*D useful flops (global)
    useful_flops_ratio: float  # model_flops / (HLO flops * n_devices)
    memory_stats: dict
    collectives: dict

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def roofline_terms(
    compiled,
    *,
    device_kind: str,
    n_devices: int,
    flops_global: float,
    bytes_per_device: float,
    wire_bytes_per_device: float,
    model_flops: float = 0.0,
) -> RooflineTerms:
    flops = flops_global / n_devices
    byts = bytes_per_device
    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_bytes_estimate": int(
            ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes
        ),
    }
    peaks = peaks_for(device_kind)
    compute_s = flops / peaks.flops
    memory_s = byts / peaks.hbm_bw
    collective_s = wire_bytes_per_device / peaks.ici_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    total_hlo = flops * n_devices
    return RooflineTerms(
        flops_per_device=flops,
        bytes_per_device=byts,
        wire_bytes_per_device=wire_bytes_per_device,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        bottleneck=bottleneck,
        model_flops=model_flops,
        useful_flops_ratio=(model_flops / total_hlo) if total_hlo else 0.0,
        memory_stats=mem,
        collectives={},
    )
