"""Three-term roofline from a compiled dry-run artifact.

    compute    = HLO_dot_FLOPs_per_device / PEAK_FLOPS
    memory     = HLO_bytes_per_device     / HBM_BW
    collective = collective_bytes_per_dev / ICI_BW

FLOPs/bytes come from the trip-count-aware HLO parser (hlo_parse.py) because
``cost_analysis()`` counts scan bodies once (verified; see tests). Shapes in
post-SPMD HLO are per-device, so all terms are per-device per step. We also
record raw cost_analysis numbers for cross-checking.

MODEL_FLOPS (the "useful work" yardstick): 6·N_active·tokens for training,
2·N_active·tokens for prefill, 2·N_active·batch for one decode step — the
standard convention (attention FLOPs excluded), so the useful-compute ratio
both exposes remat/recompute waste and (for long contexts) attention's share.
"""

from __future__ import annotations

import json
from dataclasses import asdict, dataclass

from repro.configs.base import ModelConfig, ShapeConfig
from repro.roofline import hw
from repro.roofline.hlo_parse import analyze


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    step_kind: str
    # per-device, per-step
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    # the three terms (seconds)
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    # usefulness
    model_flops_total: float
    hlo_flops_total: float
    useful_ratio: float
    # diagnostics
    collective_ops: dict
    cost_analysis_flops: float
    cost_analysis_bytes: float
    memory_stats: dict
    note: str = ""

    def terms(self) -> dict[str, float]:
        return {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    n = cfg.active_param_count()
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        return 2.0 * n * tokens
    # decode: one token per sequence per step
    return 2.0 * n * shape.global_batch


def build_roofline(
    cfg: ModelConfig,
    shape: ShapeConfig,
    mesh_name: str,
    chips: int,
    compiled,
    note: str = "",
) -> Roofline:
    totals = analyze(compiled.as_text())
    ca = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    mem_stats = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
        "output_bytes": getattr(mem, "output_size_in_bytes", 0),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
    }

    compute_s = totals.dot_flops / hw.PEAK_FLOPS_BF16
    memory_s = totals.bytes_materialized / hw.HBM_BW
    collective_s = totals.collective_bytes / hw.ICI_BW
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)

    mf = model_flops(cfg, shape)
    hlo_total = totals.dot_flops * chips
    return Roofline(
        arch=cfg.name,
        shape=shape.name,
        mesh=mesh_name,
        chips=chips,
        step_kind=shape.kind,
        flops_per_device=totals.dot_flops,
        bytes_per_device=totals.bytes_materialized,
        collective_bytes_per_device=totals.collective_bytes,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        model_flops_total=mf,
        hlo_flops_total=hlo_total,
        useful_ratio=mf / hlo_total if hlo_total else 0.0,
        collective_ops=totals.collective_ops,
        cost_analysis_flops=float(ca.get("flops", 0.0)),
        cost_analysis_bytes=float(ca.get("bytes accessed", 0.0)),
        memory_stats=mem_stats,
        note=note,
    )


def suggestion(r: Roofline) -> str:
    """One sentence on what would move the dominant term down."""
    if r.dominant == "compute":
        if r.useful_ratio < 0.4:
            return (
                "compute-bound with low useful ratio: cut recompute (remat "
                "policy) and causal-masked waste in attention tiles"
            )
        return "compute-bound near useful peak: only algorithmic FLOP cuts help"
    if r.dominant == "memory":
        return (
            "memory-bound: shrink materialized bytes (fuse/bf16 intermediates, "
            "smaller attention tiles, compressed KV cache)"
        )
    return (
        "collective-bound: reshard to cut gather volume (smaller KV gather, "
        "DROP-compressed pod all-reduce, overlap collectives with compute)"
    )


def save_report(path: str, r: Roofline) -> None:
    with open(path, "w") as f:
        json.dump(asdict(r), f, indent=2)
