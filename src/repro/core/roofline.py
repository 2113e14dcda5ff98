"""Roofline-term derivation from compiled dry-run artifacts.

Per (arch × shape × mesh × device kind):
    compute    = HLO_FLOPs   / (chips × peak bf16 FLOP/s)
    memory     = HLO_bytes   / (chips × HBM bytes/s)
    collective = coll_bytes  / (chips × ICI bytes/s)

Per-device FLOPs, bytes and collective bytes come from the loop-aware HLO
analyzer (``repro.core.hlo_cost``) and are scaled to the whole mesh.

Hardware constants come from ``PEAKS``, keyed by ``device_kind`` as JAX
reports it; a kind that is not in the table is an error, not a default.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np

# Per-chip peaks: (bf16 FLOP/s, HBM bytes/s, chip-to-chip interconnect
# bytes/s).  Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s
# bf16, 819 GB/s HBM, 1,600 Gbit/s ICI.
PEAKS = {
    "TPU v5 lite": (197e12, 819e9, 1600e9 / 8),
}


def peaks(device_kind: str) -> tuple[float, float, float]:
    """(peak bf16 FLOP/s, HBM bytes/s, ICI bytes/s) of one chip."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(type_str: str) -> int:
    """Sum byte sizes of every array shape in an HLO type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-collective-kind bytes moved, parsed from optimized HLO.

    Convention (documented in EXPERIMENTS.md): all-reduce counts 2× its
    result bytes (reduce-scatter + all-gather phases); reduce-scatter counts
    its operand bytes; all-gather / all-to-all / collective-permute count
    result bytes.  The (n-1)/n ring factor is folded to 1.
    """
    out = {k: 0.0 for k in _COLL_KINDS}
    for line in hlo_text.splitlines():
        # e.g.  %ar = (f32[128,1024]) all-reduce(f32[128,1024] %x), ...
        m = re.search(r"=\s*(\([^)]*\)|[\w\[\],{}\s]*?)\s+"
                      r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                      r"collective-permute)(-start|-done)?\(", line)
        if not m:
            continue
        result_t, kind, phase = m.group(1), m.group(2), m.group(3)
        if phase == "-done":
            continue                                 # counted at -start
        res_bytes = _shape_bytes(result_t)
        if kind == "all-reduce":
            out[kind] += 2.0 * res_bytes
        elif kind == "reduce-scatter":
            operand_t = line[m.end():]
            out[kind] += float(_shape_bytes(operand_t.split(")")[0]))
        else:
            out[kind] += float(res_bytes)
    return out


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: float
    device_kind: str
    coll_breakdown: dict = field(default_factory=dict)
    model_flops: float = 0.0
    attn_flops: float = 0.0
    per_device_peak_bytes: float = 0.0
    dot_by_tag: dict = field(default_factory=dict)

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.chips * peaks(self.device_kind)[0])

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.chips * peaks(self.device_kind)[1])

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / (self.chips * peaks(self.device_kind)[2])

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        """Roofline lower bound on step time (perfect overlap)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """MODEL_FLOPS-based MFU upper bound at the roofline step time."""
        ideal = self.model_flops / (self.chips * peaks(self.device_kind)[0])
        return ideal / self.t_bound if self.t_bound else 0.0

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "device_kind": self.device_kind,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "coll_bytes": self.coll_bytes,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
            "per_device_peak_bytes": self.per_device_peak_bytes,
            **{f"coll_{k}": v for k, v in self.coll_breakdown.items()},
            **{f"dot_{k}": v for k, v in self.dot_by_tag.items()},
        }


def analyze(compiled, *, arch: str, shape, mesh, device_kind: str,
            model_flops: float, attn_flops: float = 0.0,
            hlo_text: str | None = None) -> RooflineReport:
    """Derive roofline terms from the compiled per-device SPMD module.

    Uses the loop-aware HLO analyzer (repro.core.hlo_cost) — XLA's own
    cost_analysis() counts scan bodies once and is per-device, which
    undercounts scanned layer stacks by ~n_layers.
    """
    from repro.core import hlo_cost

    chips = int(np.prod(list(mesh.shape.values())))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    cost = hlo_cost.analyze_text(text)
    flops = cost.flops * chips                   # per-device → global
    byts = cost.bytes * chips
    coll = {k: v * chips for k, v in cost.coll.items()}
    ma = compiled.memory_analysis()
    peak = 0.0
    if ma is not None:
        tot = (getattr(ma, "temp_size_in_bytes", 0)
               + getattr(ma, "argument_size_in_bytes", 0)
               + getattr(ma, "output_size_in_bytes", 0)
               - getattr(ma, "alias_size_in_bytes", 0))
        peak = tot / chips
    return RooflineReport(
        arch=arch, shape=getattr(shape, "name", str(shape)),
        mesh="x".join(str(v) for v in mesh.shape.values()),
        chips=chips, hlo_flops=flops, hlo_bytes=byts,
        coll_bytes=sum(coll.values()), device_kind=device_kind,
        coll_breakdown=coll,
        model_flops=model_flops, attn_flops=attn_flops,
        per_device_peak_bytes=peak,
        dot_by_tag={k: v * chips for k, v in cost.dot_by_tag.items()})
