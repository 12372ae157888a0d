"""Three-term roofline analysis from compiled XLA artifacts (no hardware).

    compute    = HLO_FLOPs_global   / (chips × peak_FLOP/s)
    memory     = HLO_bytes_global   / (chips × HBM_bw)
    collective = collective_bytes   / (chips × link_bw)

``cost_analysis()`` of an SPMD-partitioned module reports the PER-DEVICE
program (verified empirically), so global = per-device × chips and each
term conveniently reduces to per-device work / per-device bandwidth.

collective_bytes is parsed from the compiled HLO: for every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
we take operand sizes (the prompt's definition):
    all-reduce: operand == result;  all-gather: result/N;
    reduce-scatter: result×N;       all-to-all, collective-permute: result.
A ring-model per-device traffic estimate is reported alongside
(all-reduce ≈ 2×, others ≈ 1× payload).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

# ---- hardware constants (per chip; no default chip) -------------------------


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float                 # bf16 FLOP/s
    hbm_bw: float                     # bytes/s
    ici_bw: float                     # bytes/s per link
    hbm_bytes: float

    @classmethod
    def for_arch(cls, arch: str) -> "HW":
        """Preset registry — the roofline terms are only meaningful
        relative to a concrete chip, so benches/tables take an ``--arch``
        flag instead of silently assuming v5e."""
        try:
            return cls(**_HW_PRESETS[arch])
        except KeyError:
            raise ValueError(
                f"unknown arch {arch!r}; known presets: "
                f"{sorted(_HW_PRESETS)}") from None

    @classmethod
    def for_device(cls, device=None) -> "HW":
        """Peaks of the chip at hand (default: the first device), keyed
        by its ``device_kind``. A kind with no preset — the CPU among
        them — is an error: name an arch with ``for_arch`` instead."""
        if device is None:
            import jax
            device = jax.devices()[0]
        arch = _DEVICE_KINDS.get(device.device_kind)
        if arch is None:
            raise ValueError(
                f"no roofline peaks for device kind {device.device_kind!r} "
                f"({device.platform}); known kinds: "
                f"{sorted(_DEVICE_KINDS)}")
        return cls.for_arch(arch)


# Public per-chip numbers: bf16 peak, HBM bandwidth, per-link ICI, HBM size.
_HW_PRESETS: Dict[str, dict] = {
    "v4": dict(peak_flops=275e12, hbm_bw=1228e9, ici_bw=50e9,
               hbm_bytes=32e9),
    "v5e": dict(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                hbm_bytes=16e9),
    "v5p": dict(peak_flops=459e12, hbm_bw=2765e9, ici_bw=100e9,
                hbm_bytes=95e9),
    "v6e": dict(peak_flops=918e12, hbm_bw=1640e9, ici_bw=100e9,
                hbm_bytes=32e9),
}

# ``jax.Device.device_kind`` -> preset (v5e reports "TPU v5 lite").
_DEVICE_KINDS: Dict[str, str] = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5": "v5p",
    "TPU v6 lite": "v6e",
}


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\w+\[[^\]]*\](?:\{[^}]*\})?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\b")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LEGACY_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _shape_bytes(typespec: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(typespec):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    result_bytes: int
    group_size: int

    @property
    def operand_bytes(self) -> int:
        if self.kind == "all-gather":
            return self.result_bytes // max(self.group_size, 1)
        if self.kind == "reduce-scatter":
            return self.result_bytes * self.group_size
        return self.result_bytes

    @property
    def traffic_bytes(self) -> int:
        """Ring-model per-device traffic."""
        n = max(self.group_size, 1)
        frac = (n - 1) / n if n > 1 else 0.0
        if self.kind == "all-reduce":
            return int(2 * self.result_bytes * frac)
        if self.kind == "all-gather":
            return int(self.result_bytes * frac)
        if self.kind == "reduce-scatter":
            return int(self.result_bytes * self.group_size * frac)
        return int(self.result_bytes * frac)


def parse_collectives(hlo_text: str) -> List[CollectiveOp]:
    ops: List[CollectiveOp] = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        typespec, kind, suffix = m.groups()
        if suffix == "-done":
            continue
        rb = _shape_bytes(typespec)
        gm = _GROUPS_RE.search(line)
        if gm:
            gsize = int(gm.group(2))
        else:
            gl = _GROUPS_LEGACY_RE.search(line)
            gsize = len(gl.group(1).split(",")) if gl else 1
        ops.append(CollectiveOp(kind, rb, gsize))
    return ops


def model_flops(n_params: float, n_tokens: float, kind: str) -> float:
    """6·N·D for train (fwd+bwd), 2·N·D for inference forward."""
    return (6.0 if kind == "train" else 2.0) * n_params * n_tokens


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_dev: float
    bytes_per_dev: float
    coll_operand_bytes: int           # prompt-faithful sum (per device prog)
    coll_traffic_bytes: int           # ring model
    coll_by_kind: Dict[str, int]
    peak_mem_bytes: int
    arg_bytes: int
    model_flops_global: float
    hw: HW
    xla_flops_per_dev: float = 0.0     # XLA cost_analysis cross-check
    xla_bytes_per_dev: float = 0.0
    bytes_by_tag: Dict[str, float] = dataclasses.field(default_factory=dict)
    kernel_io_bytes: float = 0.0       # analytic Pallas-kernel HBM traffic

    # ---- kernel-substituted memory term --------------------------------
    # On real TPU the sdpa/ssd scopes execute as Pallas kernels whose
    # intermediates stay in VMEM; their XLA-fallback HBM traffic is
    # replaced by the kernels' in/out tensors (computed analytically).
    @property
    def bytes_per_dev_kernel(self) -> float:
        replaced = sum(self.bytes_by_tag.get(t, 0.0) for t in ("sdpa", "ssd"))
        return self.bytes_per_dev - replaced + self.kernel_io_bytes

    @property
    def t_memory_kernel(self) -> float:
        return self.bytes_per_dev_kernel / self.hw.hbm_bw

    @property
    def t_bound_kernel(self) -> float:
        return max(self.t_compute, self.t_memory_kernel, self.t_collective)

    @property
    def roofline_fraction_kernel(self) -> float:
        if self.t_bound_kernel == 0:
            return 0.0
        return (self.model_flops_global / self.chips / self.t_bound_kernel
                / self.hw.peak_flops)

    # ---- the three terms, in seconds ----
    @property
    def t_compute(self) -> float:
        return self.flops_per_dev / self.hw.peak_flops

    @property
    def t_memory(self) -> float:
        return self.bytes_per_dev / self.hw.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_operand_bytes / self.hw.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def flops_global(self) -> float:
        return self.flops_per_dev * self.chips

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs_global — remat/dispatch waste detector."""
        if self.flops_global == 0:
            return 0.0
        return self.model_flops_global / self.flops_global

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline achieved if the step runs at the
        bound: useful model FLOPs per chip-second over peak."""
        if self.t_bound == 0:
            return 0.0
        return (self.model_flops_global / self.chips / self.t_bound
                / self.hw.peak_flops)

    def row(self) -> Dict[str, object]:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "hlo_gflops_dev": self.flops_per_dev / 1e9,
            "hbm_gb_dev": self.bytes_per_dev / 1e9,
            "coll_gb_dev": self.coll_operand_bytes / 1e9,
            "peak_mem_gb_dev": self.peak_mem_bytes / 1e9,
            "model_gflops_global": self.model_flops_global / 1e9,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def attn_kernel_io_bytes(cfg, n_tokens_global: int, mesh, kind: str) -> float:
    """Analytic per-device HBM traffic of the flash-attention + SSD Pallas
    kernels (q/k/v/out tensors only — intermediates live in VMEM).
    Train ≈ 3× forward (bwd recompute + grads)."""
    tp = mesh.shape.get("model", 1)
    dp = max(1, mesh.size // tp)
    t_l = max(1, n_tokens_global // dp)
    mult = 3.0 if kind == "train" else 1.0
    total = 0.0
    hd = cfg.resolved_head_dim
    if cfg.num_heads:
        n_attn = cfg.num_layers if cfg.family != "hybrid" else len(
            cfg.hybrid_layer_ids)
        if cfg.is_encdec:
            n_attn = cfg.num_encoder_layers + 2 * cfg.num_layers
        per_layer = t_l * hd * 2.0 * (2.0 * cfg.num_heads / tp
                                      + 2.0 * cfg.num_kv_heads)
        total += n_attn * per_layer
    if cfg.ssm is not None:
        from repro.models.ssm import dims as ssm_dims
        d_in, nh, ch = ssm_dims(cfg.d_model, cfg.ssm)
        n_ssm = cfg.num_layers
        per_layer = t_l * 2.0 * (2.0 * d_in / tp + 2.0 * cfg.ssm.state_dim)
        total += n_ssm * per_layer
    return total * mult


def analyze_compiled(compiled, *, arch: str, shape: str, mesh_name: str,
                     chips: int, n_params: float, n_tokens: float,
                     kind: str, hw: Optional[HW] = None) -> RooflineReport:
    """Costs come from the trip-count-aware HLO analyzer (hlo_costs.py);
    XLA's cost_analysis undercounts scanned loop bodies (counts the body
    once) and is kept only as a cross-check field. ``hw`` defaults to
    the chip at hand (``HW.for_device``)."""
    from repro.roofline.hlo_costs import analyze_hlo

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    txt = compiled.as_text()
    hc = analyze_hlo(txt)
    peak = getattr(ma, "peak_memory_in_bytes", 0) or (
        ma.argument_size_in_bytes + ma.temp_size_in_bytes +
        ma.output_size_in_bytes)
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_dev=hc.flops,
        bytes_per_dev=hc.hbm_bytes,
        coll_operand_bytes=int(hc.collective_operand_bytes),
        coll_traffic_bytes=int(hc.collective_traffic_bytes),
        coll_by_kind={k: int(v) for k, v in hc.coll_by_kind.items()},
        peak_mem_bytes=int(peak),
        arg_bytes=int(ma.argument_size_in_bytes),
        model_flops_global=model_flops(n_params, n_tokens, kind),
        hw=hw or HW.for_device(),
        xla_flops_per_dev=float(ca.get("flops", 0.0)),
        xla_bytes_per_dev=float(ca.get("bytes accessed", 0.0)),
        bytes_by_tag=dict(hc.bytes_by_tag),
    )


@dataclasses.dataclass(frozen=True)
class IntensityProfile:
    """A job's measured compute-vs-memory character, distilled from its
    compiled program's roofline terms — the per-job signal the
    ``ModePlanner`` consumes (core/spatial.py ``measured_interference``).

    ``arithmetic_intensity`` is FLOPs per HBM byte (the roofline x-axis);
    ``memory_bound_frac`` is the share of the three roofline terms spent
    in HBM — near 1 for decode-style bandwidth-bound steps, near 0 for
    MXU-bound packed training. The latter is what the planner uses: two
    memory-bound jobs sharing a chip contend for the one resource that is
    already the bottleneck, while compute-bound jobs pack benignly.
    """
    arithmetic_intensity: float
    memory_bound_frac: float
    bottleneck: str

    @classmethod
    def from_report(cls, r: RooflineReport) -> "IntensityProfile":
        ai = (r.flops_per_dev / r.bytes_per_dev) if r.bytes_per_dev else 0.0
        total = r.t_compute + r.t_memory + r.t_collective
        mbf = (r.t_memory / total) if total else 0.0
        return cls(arithmetic_intensity=ai, memory_bound_frac=mbf,
                   bottleneck=r.bottleneck)

    @classmethod
    def from_compiled(cls, compiled, hw: Optional[HW] = None) -> "IntensityProfile":
        """Directly from a compiled XLA program (no model metadata needed)
        — the form the scheduler records at first dispatch, the way
        ``MemoryAdmission.record_measured`` records HBM bytes. ``hw``
        defaults to the chip at hand (``HW.for_device``)."""
        from repro.roofline.hlo_costs import analyze_hlo
        hc = analyze_hlo(compiled.as_text())
        hw = hw or HW.for_device()
        tc = hc.flops / hw.peak_flops
        tm = hc.hbm_bytes / hw.hbm_bw
        tl = hc.collective_operand_bytes / hw.ici_bw
        total = tc + tm + tl
        terms = {"compute": tc, "memory": tm, "collective": tl}
        return cls(
            arithmetic_intensity=(hc.flops / hc.hbm_bytes)
            if hc.hbm_bytes else 0.0,
            memory_bound_frac=(tm / total) if total else 0.0,
            bottleneck=max(terms, key=terms.get))

    @property
    def interference(self) -> float:
        """The planner-facing interference intensity in [0, 1]."""
        return min(1.0, max(0.0, self.memory_bound_frac))
