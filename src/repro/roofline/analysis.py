"""Roofline-term extraction from compiled XLA artifacts.

Per (arch × shape × mesh) cell the dry-run produces a lowered+compiled
module; from it we derive the three roofline terms on TPU v5e:

  compute    = HLO_FLOPs           / (peak_FLOP/s per chip)
  memory     = HLO_bytes_accessed  / (HBM bytes/s per chip)
  collective = Σ collective bytes  / (ICI bytes/s per chip)

``cost_analysis`` on an SPMD-partitioned module reports *per-device*
flops/bytes, so no per-chip division is needed; collective bytes are NOT
in cost_analysis — we parse the post-SPMD HLO text and sum the result
shapes of all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute ops (each counted once per executed instruction,
with while-loop trip counts applied when derivable from scan bounds).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

# TPU v5e hardware constants (assignment brief)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (serialized-link model)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  bf16[16,256,512]{2,1,0}   or   f32[]   (scalars)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def shape_bytes(shape_str: str) -> int:
    """Total bytes of one HLO shape string (tuples handled by caller)."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: Dict[str, int]       # per-chip wire bytes (ring model)
    count_by_kind: Dict[str, int]
    result_bytes_by_kind: Dict[str, int]  # raw result-shape bytes

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _group_size(line: str) -> int:
    m = _GROUPS_V2_RE.search(line)
    if m:
        return int(m.group(2))
    # legacy {{0,1,...},{...}} format: size of the first group
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
    if m:
        return m.group(1).count(",") + 1
    return 2


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Per-chip wire bytes of every collective instruction (ring model).

    Result-shape bytes are a poor cost proxy because XLA freely rewrites
    all-reduce <-> reduce-scatter + all-gather (same wire traffic, 2x the
    result bytes).  Ring-algorithm wire bytes per chip, result size S,
    group size n:
        all-reduce          2.S.(n-1)/n      (reduce + broadcast phases)
        all-gather          S.(n-1)/n        (S = full gathered result)
        reduce-scatter      S.(n-1)          (S = the scattered shard)
        all-to-all          S.(n-1)/n
        collective-permute  S
    Trip counts of scan loops are handled by the caller via the
    two-point depth fit (cost_configs), not here."""
    bytes_by: Dict[str, float] = {k: 0 for k in _COLLECTIVES}
    result_by: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    count_by: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # result shape appears before ' = ... <op>('
        for kind in _COLLECTIVES:
            if f" {kind}(" in s or f" {kind}-start(" in s:
                lhs = s.split(" = ", 1)
                if len(lhs) != 2:
                    continue
                shape_part = lhs[1].split(kind, 1)[0]
                size = shape_bytes(shape_part)
                # XLA:CPU promotes bf16 reductions to f32 ("..._promoted"
                # reducers); TPU runs them native bf16 -- count half.
                if kind == "all-reduce" and "promoted" in s \
                        and "f32[" in shape_part:
                    size //= 2
                n = _group_size(s)
                if kind == "all-reduce":
                    wire = 2.0 * size * (n - 1) / n
                elif kind == "reduce-scatter":
                    wire = float(size) * (n - 1)
                elif kind == "collective-permute":
                    wire = float(size)
                else:  # all-gather / all-to-all
                    wire = float(size) * (n - 1) / n
                bytes_by[kind] += wire
                result_by[kind] += size
                count_by[kind] += 1
                break
    return CollectiveStats({k: int(v) for k, v in bytes_by.items()},
                           count_by, result_by)


def while_trip_counts(hlo_text: str) -> List[int]:
    """Best-effort trip counts of while loops (scan emits constant trip
    counts as a comparison against an iteration bound constant)."""
    # xla renders known trip counts in backend_config or in the condition
    # root: constant(<n>); this is heuristic and only used for reporting.
    counts = []
    for m in re.finditer(r"trip_count[\"']?[:=]\s*(\d+)", hlo_text):
        counts.append(int(m.group(1)))
    return counts


@dataclasses.dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    flops: float                 # per-chip, from cost_analysis
    hbm_bytes: float             # per-chip, from cost_analysis
    collective_bytes: float      # per-chip HLO static sum (see note)
    t_compute: float
    t_memory: float
    t_collective: float
    model_flops: float           # 6·N·D (train) / 2·N·D (decode), global
    per_device_argument_bytes: float
    peak_memory_bytes: float
    collective_counts: Dict[str, int]

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def bounded_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs × chips): >1 means HLO under-counts
        (e.g. fused ops), <1 means remat/dispatch overhead."""
        if self.flops <= 0:
            return 0.0
        n_chips = {"16x16": 256, "2x16x16": 512}.get(self.mesh, 256)
        return self.model_flops / (self.flops * n_chips)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline achieved if the step runs in
        bounded_time: useful model flops / (chips · peak · bounded_time)."""
        n_chips = {"16x16": 256, "2x16x16": 512}.get(self.mesh, 256)
        denom = n_chips * PEAK_FLOPS * self.bounded_time
        return self.model_flops / denom if denom > 0 else 0.0


def model_flops(cfg, shape) -> float:
    """6·N_active·D for training, 2·N_active·D per generated token for
    decode, 2·N·D for prefill (forward only)."""
    from repro.models.registry import count_params
    n_active = count_params(cfg, active_only=True)
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * tokens
    # decode: one token per sequence in the batch
    return 2.0 * n_active * shape.global_batch


def extract(compiled, lowered_text: Optional[str], cfg, shape,
            mesh_label: str) -> RooflineTerms:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))

    text = lowered_text if lowered_text is not None else compiled.as_text()
    colls = parse_collectives(text)

    mem = compiled.memory_analysis()
    arg_bytes = float(getattr(mem, "argument_size_in_bytes", 0) or 0)
    temp = float(getattr(mem, "temp_size_in_bytes", 0) or 0)
    out_b = float(getattr(mem, "output_size_in_bytes", 0) or 0)

    return RooflineTerms(
        arch=cfg.name, shape=shape.name, mesh=mesh_label,
        flops=flops, hbm_bytes=hbm,
        collective_bytes=float(colls.total_bytes),
        t_compute=flops / PEAK_FLOPS,
        t_memory=hbm / HBM_BW,
        t_collective=colls.total_bytes / ICI_BW,
        model_flops=model_flops(cfg, shape),
        per_device_argument_bytes=arg_bytes,
        peak_memory_bytes=arg_bytes + temp + out_b,
        collective_counts={k: v for k, v in colls.count_by_kind.items()
                           if v},
    )


def format_row(t: RooflineTerms) -> str:
    return (f"{t.arch:>22} {t.shape:>12} {t.mesh:>8} "
            f"{t.flops:>12.3e} {t.hbm_bytes:>12.3e} "
            f"{t.collective_bytes:>12.3e} "
            f"{t.t_compute * 1e3:>10.2f} {t.t_memory * 1e3:>10.2f} "
            f"{t.t_collective * 1e3:>10.2f} {t.dominant:>10} "
            f"{t.useful_flops_ratio:>8.3f} {t.roofline_fraction:>8.3f} "
            f"{t.per_device_argument_bytes / 2**30:>8.2f}")


HEADER = (f"{'arch':>22} {'shape':>12} {'mesh':>8} "
          f"{'flops/chip':>12} {'bytes/chip':>12} {'coll_B/chip':>12} "
          f"{'t_comp_ms':>10} {'t_mem_ms':>10} {'t_coll_ms':>10} "
          f"{'dominant':>10} {'useful':>8} {'roofline':>8} {'argGiB':>8}")


# ------------------------------------------------- two-point depth fit
def cost_configs(cfg):
    """Depth-reduced, inner-scan-free config pair for exact cost fitting.

    XLA's HloCostAnalysis counts while-loop bodies ONCE (trip counts are
    annotated but not applied), so a scan-over-layers module under-reports
    flops/bytes by ~n_layers×.  Fix: compile the same cell at two depths
    (d1, d2) with every *inner* scan disabled (attention/MoE/Mamba
    chunking off — identical math, no nested loops), then extrapolate
    affinely: cost(L) = c(d1) + (c(d2) − c(d1)) · (L − d1) / (d2 − d1).
    The remaining outer scan-over-layers has its body counted once per
    compile, which the affine fit absorbs exactly because layers are
    homogeneous (per-family period groups for jamba).

    Returns (cfg_d1, cfg_d2, d1, d2, L_units) or None when the family has
    no outer scan (xLSTM is unrolled: its reported costs are already
    correct, modulo the sLSTM time-scan noted in slstm_correction()).
    """
    kill_inner = dict(attn_chunk=0, moe_chunk=0, mamba_chunk=0,
                      scan_unroll=True, grad_accum=1)
    if cfg.family == "ssm":
        return None
    if cfg.family == "hybrid":
        p = cfg.attn_period or 1
        return (cfg.scaled(n_layers=p, **kill_inner),
                cfg.scaled(n_layers=2 * p, **kill_inner),
                1, 2, cfg.n_layers // p)
    if cfg.family == "audio":
        return (cfg.scaled(n_layers=1, n_encoder_layers=1, **kill_inner),
                cfg.scaled(n_layers=2, n_encoder_layers=2, **kill_inner),
                1, 2, cfg.n_layers)
    return (cfg.scaled(n_layers=1, **kill_inner),
            cfg.scaled(n_layers=2, **kill_inner),
            1, 2, cfg.n_layers)


def affine_fit(c1: float, c2: float, d1: int, d2: int, L: int) -> float:
    return c1 + (c2 - c1) * (L - d1) / float(d2 - d1)


def slstm_correction_flops(cfg, shape) -> float:
    """sLSTM's time recurrence is a lax.scan over seq_len whose body the
    HLO cost analysis counts once; add the missing (L_time − 1) bodies
    analytically (recurrent per-head matmul dominates):
        flops/step = 2 · b · H · hd · 4hd = 8 · b · d · hd
    """
    if cfg.family != "ssm" or not cfg.slstm_layers:
        return 0.0
    b = shape.global_batch
    l = shape.seq_len if shape.kind != "decode" else 1
    d = cfg.d_model
    hd = d // cfg.n_heads
    per_step = 8.0 * b * d * hd
    return len(cfg.slstm_layers) * max(0, l - 1) * per_step


def raw_costs(compiled, hlo_text: Optional[str] = None) -> Dict[str, float]:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    text = hlo_text if hlo_text is not None else compiled.as_text()
    colls = parse_collectives(text)
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(colls.total_bytes)}
