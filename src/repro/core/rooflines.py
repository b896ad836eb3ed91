"""Three-term roofline extraction from compiled XLA artifacts (§Roofline).

  compute term    = HLO_FLOPs        / (chips * PEAK_FLOPS)
  memory term     = HLO_bytes        / (chips * HBM_BW)
  collective term = collective_bytes / (chips * LINK_BW)

``cost_analysis()`` provides FLOPs and bytes accessed.  Collective bytes are
not in cost_analysis, so we parse the (optimized when available) HLO text and
sum operand sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

# TPU v5e constants (assignment-specified)
PEAK_FLOPS_BF16 = 197e12     # per chip
HBM_BW = 819e9               # B/s per chip
LINK_BW = 50e9               # B/s per ICI link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# matches e.g. f32[256,4096]{1,0} or bf16[8,128] — the *result* shape of an op
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    nbytes = _DTYPE_BYTES.get(dtype)
    if nbytes is None:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * nbytes


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes of every collective op in an HLO dump.

    Uses the result shape (for all-gather that's the gathered size, for
    reduce-scatter the scattered size) as the per-device wire-cost proxy;
    all-reduce is counted 2x (reduce-scatter + all-gather decomposition).
    """
    out: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        # result shape appears after '=' : "%x = f32[..]{..} all-gather(...)"
        m = re.search(r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^=]*?\b(" +
                      "|".join(_COLLECTIVES) + r")\b", s)
        if not m:
            # tuple-shaped results: "= (f32[..], f32[..]) all-reduce(...)"
            if not any(f" {c}(" in s or f"{c}-start" in s for c in _COLLECTIVES):
                continue
            kind = next(c for c in _COLLECTIVES
                        if f" {c}(" in s or f"{c}-start" in s)
            total = sum(_shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(
                s.split("=", 1)[1].split(kind)[0]))
            mult = 2 if kind == "all-reduce" else 1
            out[kind] += mult * total
            out["count"] += 1
            continue
        dtype, dims, kind = m.groups()
        mult = 2 if kind == "all-reduce" else 1
        out[kind] += mult * _shape_bytes(dtype, dims)
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclasses.dataclass
class Roofline:
    flops: float
    bytes_accessed: float
    coll_bytes: float
    chips: int
    # derived
    compute_s: float
    memory_s: float
    collective_s: float
    bound: str
    model_flops: float = 0.0      # 6*N*D useful-FLOPs estimate
    useful_ratio: float = 0.0     # model_flops / hlo_flops
    bytes_per_device: float = 0.0  # from memory_analysis

    def as_row(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    @property
    def step_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the ideal (compute-only) roofline this step achieves."""
        return self.compute_s / self.step_s if self.step_s else 0.0


def analyze(compiled, hlo_text: str, chips: int,
            model_flops: float = 0.0) -> Roofline:
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    bytes_accessed = float(ca.get("bytes accessed", 0.0))
    coll = collective_bytes(hlo_text)["total"]
    mem = compiled.memory_analysis()
    bpd = float(mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    # cost_analysis flops/bytes are program-wide per device under SPMD
    compute_s = flops / PEAK_FLOPS_BF16
    memory_s = bytes_accessed / HBM_BW
    collective_s = coll / LINK_BW
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bound = max(terms, key=terms.get)
    return Roofline(
        flops=flops, bytes_accessed=bytes_accessed, coll_bytes=coll,
        chips=chips, compute_s=compute_s, memory_s=memory_s,
        collective_s=collective_s, bound=bound, model_flops=model_flops,
        useful_ratio=(model_flops / flops) if flops else 0.0,
        bytes_per_device=bpd,
    )
