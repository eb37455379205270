"""Roofline terms from a compiled (dry-run) artifact.

Per (arch × shape × mesh), with the chip's published peaks (``peaks``):
  compute    = HLO_FLOPs / (chips × peak_flops)      [bf16 peak]
  memory     = HLO_bytes / (chips × hbm_bw)          [HBM bandwidth]
  collective = collective_bytes / (chips × ici_bw)   [per-link ICI]

HLO_FLOPs / HLO_bytes come from compiled.cost_analysis(); collective bytes
are not in cost_analysis, so the HLO text is parsed: we sum the *result*
bytes of every all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute (result size ~ bytes moved per device for ring
implementations; a conservative, mesh-independent proxy).

MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE); the ratio
MODEL_FLOPS / HLO_FLOPs exposes remat/redundancy overhead (ratios < 1 mean
the compiled graph does extra work: remat recompute, attention quadratic
terms, dequant copies...).
"""
from __future__ import annotations

import re
from dataclasses import asdict, dataclass

# Published per-chip peaks, keyed by JAX's ``device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" -- 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
# interconnect (ici_bw is one of its four 50 GB/s links).
V5E = "TPU v5 lite"
PEAKS: dict[str, dict[str, float]] = {
    V5E: {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


class UnknownDevice(KeyError):
    """No published peaks for this ``device_kind`` (never a default)."""


def peaks(device_kind: str) -> dict[str, float]:
    """The published peaks of one chip of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> dict[str, int]:
    """Sum result bytes of every collective op, keyed by op kind."""
    out: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # result shape appears between '=' and the op name: "%x = bf16[..] op-name("
        m = re.match(r"^[%\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", s)
        if not m:
            continue
        opname = m.group(2)
        kind = next(
            (k for k in _COLLECTIVES if opname == k or opname.startswith(k + ".")),
            None,
        )
        if kind is None:
            continue
        out[kind] += _shape_bytes(m.group(1))
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    useful_ratio: float  # MODEL_FLOPS / HLO_FLOPs
    step_time_bound_s: float
    hw_fraction: float  # compute_s / step_time_bound_s ("roofline fraction")
    note: str = ""

    def to_dict(self):
        return asdict(self)


def analyze(
    *,
    arch: str,
    shape: str,
    mesh_name: str,
    chips: int,
    cost: dict,
    hlo_text: str,
    model_flops: float,
    note: str = "",
) -> RooflineReport:
    hw = peaks(V5E)  # the dry-run meshes describe v5e chips
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes_from_hlo(hlo_text)["total"]
    # cost_analysis of the SPMD module is per-device already
    compute_s = flops / hw["peak_flops"]
    memory_s = byts / hw["hbm_bw"]
    collective_s = coll / hw["ici_bw"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    bound = max(terms.values())
    return RooflineReport(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        chips=chips,
        hlo_flops=flops,
        hlo_bytes=byts,
        collective_bytes=float(coll),
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        bottleneck=bottleneck,
        model_flops=model_flops,
        useful_ratio=(model_flops / chips) / flops if flops else 0.0,
        step_time_bound_s=bound,
        hw_fraction=compute_s / bound if bound else 0.0,
        note=note,
    )


def model_flops_for(cfg, shape_cell, train: bool) -> float:
    """6·N·D per step (3x for fwd+bwd via the standard 6ND convention)."""
    n_active = cfg.active_param_count()
    tokens = shape_cell.global_batch * (
        shape_cell.seq_len if shape_cell.kind == "train" else
        (shape_cell.seq_len if shape_cell.kind == "prefill" else 1)
    )
    mult = 6.0 if shape_cell.kind == "train" else 2.0
    return mult * n_active * tokens
