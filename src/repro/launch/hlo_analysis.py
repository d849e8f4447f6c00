"""HLO-level analysis: collective byte counts + roofline terms.

``cost_analysis()`` gives FLOPs and HBM bytes but NOT collective traffic —
we parse the optimized HLO text and sum the result-shape bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.

Hardware model (TPU v5e, per the assignment):
  peak bf16:   197 TFLOP/s per chip
  HBM bw:      819 GB/s per chip
  ICI link bw: ~50 GB/s per link
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %all-reduce.5 = bf16[16,7168]{1,0} all-reduce(...)
_OP_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?\s("
    + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(")


# e.g. ... custom_call_target="tpu_custom_call" ... metadata={op_name=
# "jit(step)/while/body/jit(_paged_attention_call)/pallas_call" ...}
_PALLAS_RE = re.compile(r"\(([\w.]+)\)/pallas_call")


def pallas_kernels(hlo_text: str) -> set:
    """Names of the jitted wrappers whose ``pallas_call`` became a TPU
    kernel (``tpu_custom_call``) in a compiled program's text.  Empty for a
    program whose kernels ran in interpret mode."""
    return {name for line in hlo_text.splitlines()
            if "tpu_custom_call" in line
            for name in _PALLAS_RE.findall(line)}


def _shape_bytes(dtype: str, dims: str) -> int:
    size = _DTYPE_BYTES.get(dtype, 4)
    for d in dims.split(","):
        if d:
            size *= int(d)
    return size


@dataclass
class CollectiveStats:
    bytes_by_kind: dict = field(default_factory=dict)
    count_by_kind: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    def add(self, kind: str, nbytes: int) -> None:
        self.bytes_by_kind[kind] = self.bytes_by_kind.get(kind, 0) + nbytes
        self.count_by_kind[kind] = self.count_by_kind.get(kind, 0) + 1


def collective_stats(hlo_text: str) -> CollectiveStats:
    stats = CollectiveStats()
    seen_done = set()
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        if "-done(" in line:  # async pair: count the start only
            continue
        dtype, dims, kind = m.groups()
        stats.add(kind, _shape_bytes(dtype, dims))
    return stats


@dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    collective_bytes: float
    n_chips: int

    @property
    def compute_s(self) -> float:
        return self.flops / (self.n_chips * PEAK_FLOPS)

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / (self.n_chips * HBM_BW)

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / (self.n_chips * LINK_BW)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes, "n_chips": self.n_chips,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
        }


def roofline_from_compiled(compiled, n_chips: int) -> Roofline:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older jax returns [dict]
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    stats = collective_stats(compiled.as_text())
    return Roofline(flops=flops, hbm_bytes=hbm,
                    collective_bytes=float(stats.total_bytes),
                    n_chips=n_chips)


def model_flops(n_params_active: int, n_tokens: int, *,
                training: bool) -> float:
    """6·N·D for training, 2·N·D for inference forward."""
    mult = 6.0 if training else 2.0
    return mult * n_params_active * n_tokens
