"""Roofline term extraction from compiled dry-run artifacts.

  compute    = HLO_FLOPs / (chips × peak bf16 FLOP/s)
  memory     = HLO_bytes / (chips × peak HBM bytes/s)
  collective = collective_bytes / (per-link ICI bytes/s)

The peaks are looked up by device kind (`jax.Device.device_kind`) in
`PEAKS`; a kind the table does not know raises instead of borrowing
another chip's numbers.

cost_analysis() provides FLOPs/bytes; collective bytes are parsed from the
compiled (post-SPMD) HLO text by summing operand sizes of all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute ops.
"""
from __future__ import annotations

import dataclasses
import re

__all__ = ["PEAKS", "peak_rates", "RooflineTerms", "collective_bytes",
           "roofline_terms", "parse_memory_analysis"]

# Peak rates per device kind. TPU v5e ("TPU v5 lite"), from Google Cloud's
# "TPU v5e" documentation: 197 TFLOP/s bf16, 819 GB/s HBM, and 1,600 Gbit/s
# of chip-to-chip interconnect, i.e. 50 GB/s on each of its four ICI links.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peak_rates(device_kind: str) -> dict:
    """The peak rates of `device_kind`; raises KeyError for a kind without
    a sourced entry in `PEAKS`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"^\s*(?:[%\w\.\-]+\s*=\s*)?"
    r"(\([^)]*\)|[a-z0-9\[\],{}\s]*?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.MULTILINE)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum *output* shape bytes per collective kind from (post-SPMD) HLO."""
    out: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        out[kind] = out.get(kind, 0) + b
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops: float                  # total HLO flops (all devices)
    hbm_bytes: float              # total bytes accessed
    coll_bytes: float             # per-device collective bytes (HLO is per-device post-SPMD)
    chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    coll_breakdown: dict
    model_flops: float = 0.0

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_fraction(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def row(self) -> dict:
        return {
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "model_flops": self.model_flops, "hlo_flops": self.flops,
            "useful_frac": round(self.useful_fraction, 4),
        }


def roofline_terms(cost: dict, hlo_text: str, chips: int, device_kind: str,
                   model_flops: float = 0.0) -> RooflineTerms:
    """cost: compiled.cost_analysis() dict. Post-SPMD cost analysis reports
    *per-device* flops; scale to the full step then divide by fleet rate
    (the peaks of `device_kind`, see `peak_rates`)."""
    hw = peak_rates(device_kind)
    flops = float(cost.get("flops", 0.0)) * chips
    hbm = float(cost.get("bytes accessed", 0.0)) * chips
    coll = collective_bytes(hlo_text)
    coll_total = float(sum(coll.values()))
    return RooflineTerms(
        flops=flops, hbm_bytes=hbm, coll_bytes=coll_total, chips=chips,
        compute_s=flops / (chips * hw["flops_bf16"]),
        memory_s=hbm / (chips * hw["hbm_bw"]),
        collective_s=coll_total / hw["ici_bw"],
        coll_breakdown=coll, model_flops=model_flops)


def parse_memory_analysis(mem) -> dict:
    """compiled.memory_analysis() → compact dict (bytes)."""
    keys = ["argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes"]
    out = {}
    for k in keys:
        out[k] = getattr(mem, k, None)
    return out
