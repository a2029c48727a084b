"""Roofline-term derivation from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), per the assignment:

  compute    = HLO_FLOPs / (chips × 197e12  bf16 FLOP/s)
  memory     = HLO_bytes / (chips × 819e9   HBM B/s)
  collective = wire_bytes / (chips × 50e9   ICI B/s per link)

``cost_analysis`` counts ``lax.scan`` bodies once (measured), so totals are
assembled as ``full_model_cost + (L-1) × per_superlayer_cost`` where the
superlayer is lowered standalone under the same mesh/shardings with fully
static loops (launch/dryrun.py builds both).

Collective wire bytes come from parsing the compiled HLO: every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
op's result shape × ring factor for its replica-group size.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

# TPU v5e-class target (constants fixed by the assignment)
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([\d,]*)\][^a-z]*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


@dataclasses.dataclass
class Collective:
    kind: str
    dtype: str
    shape: Tuple[int, ...]
    group_size: int

    @property
    def result_bytes(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n * _DTYPE_BYTES.get(self.dtype, 4)

    @property
    def wire_bytes(self) -> float:
        """Per-device bytes crossing links (ring algorithms)."""
        return self._wire(self.result_bytes)

    @property
    def wire_bytes_bf16(self) -> float:
        """Wire bytes with element size capped at 2 B. XLA:CPU upconverts
        bf16 dot operands to f32 *before* the partitioner inserts the
        collective (no bf16 FMA on CPU), inflating f32 wire 2× vs a TPU
        compile where the dot is native-bf16. This is the TPU-wire metric;
        the raw f32 number is kept alongside."""
        n = 1
        for d in self.shape:
            n *= d
        return self._wire(n * min(_DTYPE_BYTES.get(self.dtype, 4), 2))

    def _wire(self, b: float) -> float:
        g = max(self.group_size, 2)
        if self.kind == "all-reduce":
            return 2.0 * (g - 1) / g * b
        if self.kind == "all-gather":          # result = gathered (full)
            return (g - 1) / g * b
        if self.kind == "reduce-scatter":      # result = scattered (1/g)
            return (g - 1) * b
        if self.kind == "all-to-all":
            return (g - 1) / g * b
        if self.kind == "collective-permute":
            return float(b)
        return float(b)


def parse_collectives(hlo_text: str) -> List[Collective]:
    out: List[Collective] = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        if "-done" in line:
            continue
        shape = tuple(int(x) for x in dims.split(",") if x) if dims else ()
        g = 1
        mg = _GROUPS_RE.search(line)
        if mg:
            g = int(mg.group(2))
        else:
            ml = _GROUPS_LIST_RE.search(line)
            if ml:
                g = len([t for t in ml.group(1).split(",") if t.strip()])
        out.append(Collective(kind, dtype, shape, g))
    return out


def collective_wire_bytes(hlo_text: str) -> float:
    return sum(c.wire_bytes for c in parse_collectives(hlo_text))


def collective_wire_bytes_bf16(hlo_text: str) -> float:
    return sum(c.wire_bytes_bf16 for c in parse_collectives(hlo_text))


def collective_summary(hlo_text: str) -> Dict[str, Dict[str, float]]:
    summ: Dict[str, Dict[str, float]] = {}
    for c in parse_collectives(hlo_text):
        e = summ.setdefault(c.kind, {"count": 0, "wire_bytes": 0.0})
        e["count"] += 1
        e["wire_bytes"] += c.wire_bytes
    return summ


# ---------------------------------------------------------------------------
# Term assembly
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CellCost:
    """Costs for one lowering (full model counts scan body once)."""
    flops: float                 # whole-program HLO flops
    bytes_accessed: float
    wire_bytes: float
    collectives: Dict[str, Dict[str, float]]
    wire_bytes_bf16: float = 0.0  # dtype-capped (TPU-native-bf16 wire)


@dataclasses.dataclass
class Roofline:
    """``flops``/``bytes_accessed``/``wire_bytes`` are the *per-device* SPMD
    program costs (XLA partitions before cost analysis); the spec formula
    HLO_FLOPs/(chips × peak) is applied with HLO_FLOPs = per-device × chips,
    which reduces to per-device / peak."""
    arch: str
    shape: str
    chips: int
    flops: float                 # per-device, assembled (per step)
    bytes_accessed: float
    wire_bytes: float
    model_flops: float           # 6·N_active·D analytic (GLOBAL)
    wire_bytes_bf16: float = 0.0
    min_bytes: float = 0.0       # analytic min HBM traffic (GLOBAL; decode)
    kind: str = "train"
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    collective_bf16_s: float = 0.0

    def __post_init__(self):
        self.compute_s = (self.flops * self.chips) / (self.chips * PEAK_FLOPS)
        self.memory_s = (self.bytes_accessed * self.chips) / (self.chips * HBM_BW)
        self.collective_s = (self.wire_bytes * self.chips) / (self.chips * ICI_BW)
        self.collective_bf16_s = ((self.wire_bytes_bf16 or self.wire_bytes)
                                  * self.chips) / (self.chips * ICI_BW)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Roofline step-time lower bound = max of overlappable terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / global HLO_FLOPs — catches remat/redundancy waste."""
        total = self.flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Ideal-step time over dominant-term time (1.0 = at the roofline).

        train/prefill (compute-dominated ideals): ideal = MODEL_FLOPS at
        peak. decode (inherently bandwidth-bound): ideal = minimum HBM
        traffic (params + KV/state read) at full HBM bandwidth."""
        if self.kind == "decode":
            ideal = self.min_bytes / (self.chips * HBM_BW)
        else:
            ideal = self.model_flops / (self.chips * PEAK_FLOPS)
        return ideal / self.step_s if self.step_s else 0.0

    def to_dict(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "chips": self.chips,
            "flops": self.flops, "bytes": self.bytes_accessed,
            "wire_bytes": self.wire_bytes, "model_flops": self.model_flops,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "collective_bf16_s": self.collective_bf16_s,
            "bottleneck": self.bottleneck,
            "step_s": self.step_s,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def assemble(arch: str, shape, chips: int,
             full: CellCost, layer: Optional[CellCost],
             n_bodies: int, model_flops: float,
             min_bytes: float = 0.0, kind: str = "train") -> Roofline:
    """total = full (scan body counted once) + (n_bodies-1) × layer."""
    extra = max(n_bodies - 1, 0)
    if layer is None:
        extra = 0
        layer = CellCost(0, 0, 0, {})
    return Roofline(
        arch=arch, shape=shape, chips=chips,
        flops=full.flops + extra * layer.flops,
        bytes_accessed=full.bytes_accessed + extra * layer.bytes_accessed,
        wire_bytes=full.wire_bytes + extra * layer.wire_bytes,
        wire_bytes_bf16=(full.wire_bytes_bf16
                         + extra * layer.wire_bytes_bf16),
        model_flops=model_flops, min_bytes=min_bytes, kind=kind,
    )


def model_flops_estimate(cfg, shape) -> float:
    """6·N_active·D for training; 2·N_active·D for inference (per step)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def report(jsonl_path: str) -> str:
    """Markdown §Roofline table from the dry-run artifacts."""
    cells = {}
    mems = {}
    for line in open(jsonl_path):
        try:
            r = json.loads(line)
        except json.JSONDecodeError:
            continue
        if not r.get("ok"):
            continue
        key = (r["arch"], r["shape"])
        if r["mesh"] == "single" and "roofline" in r:
            cells[key] = r
        mems[(r["arch"], r["shape"], r["mesh"])] = \
            r["memory"]["per_device_total"] / 2 ** 30

    out = ["| arch | shape | compute s | memory s | collective s | "
           "bottleneck | roofline frac | useful FLOPs | GiB/dev (1 pod) | "
           "GiB/dev (2 pod) |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for (arch, shape), r in sorted(cells.items()):
        ro = r["roofline"]
        m1 = mems.get((arch, shape, "single"), float("nan"))
        m2 = mems.get((arch, shape, "multi"), float("nan"))
        out.append(
            f"| {arch} | {shape} | {ro['compute_s']:.4f} | "
            f"{ro['memory_s']:.4f} | {ro['collective_s']:.4f} | "
            f"{ro['bottleneck']} | {ro['roofline_fraction']:.3f} | "
            f"{ro['useful_flops_ratio']:.3f} | {m1:.1f} | {m2:.1f} |")
    return "\n".join(out)


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--artifacts", default="benchmarks/artifacts/dryrun.jsonl")
    args = ap.parse_args()
    print(report(args.artifacts))


def min_bytes_estimate(cfg, shape) -> float:
    """Analytic minimum GLOBAL HBM traffic for one decode step: every active
    parameter read once (bf16) + the KV/state cache read once."""
    pbytes = 2.0 * cfg.active_param_count()
    cache = 0.0
    B, S = shape.global_batch, shape.seq_len
    pat = cfg.superlayer_pattern
    n_attn_layers = 0
    for kind in pat:
        if kind.startswith("attn") or kind == "shared_attn":
            n_attn_layers += 1
    n_attn = cfg.num_superlayers * n_attn_layers
    if cfg.num_heads:
        w = cfg.window_size or S
        # local layers read only the window
        if cfg.attn_kind == "local_global" and cfg.local_per_global:
            n_local = cfg.num_superlayers * cfg.local_per_global
            n_global = cfg.num_superlayers
            cache += n_local * B * min(w, S) * cfg.kv_dim * 2 * 2
            cache += n_global * B * S * cfg.kv_dim * 2 * 2
        else:
            cache += n_attn * B * S * cfg.kv_dim * 2 * 2
    if cfg.ssm_kind == "mamba2":
        n_ssm = cfg.num_layers
        cache += (n_ssm * B * cfg.ssm_nheads * cfg.ssm_head_dim
                  * cfg.ssm_state * 4)
    if cfg.ssm_kind == "rwkv6":
        nh = cfg.d_model // cfg.ssm_head_dim
        cache += cfg.num_layers * B * nh * cfg.ssm_head_dim ** 2 * 4
    return pbytes + cache


if __name__ == "__main__":
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    main()
