"""Multi-precision policy (paper §III-E4 -> TPU).

Ara subdivides its 64-bit lane datapath: 1x64 / 2x32 / 4x16 / 8x8 per cycle
— throughput doubles per precision halving. The TPU analogue: MXU bf16 at
197 TFLOP/s vs fp32 at ~0.5x, plus int8 at ~2x (v5e 394 TOPS). This module
is the single source for per-precision peaks (roofline denominators) and
the cast policy used by models (params fp32/bf16 master, compute dtype
configurable, fp32 accumulation — matching the kernels' behaviour).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

# TPU v5e per-chip peaks
PEAKS_FLOPS = {
    "float32": 98.5e12,      # ~0.5x bf16 (fp32 via MXU passes)
    "bfloat16": 197e12,
    "float16": 197e12,
    "int8": 394e12,
}

# Ara's per-precision peak (FLOP/cycle/lane), the paper's datapath split.
# SINGLE SOURCE for the multi-precision speedup claim: AraConfig
# .peak_flop_per_cycle, perfmodel's per-ew utilization, and the kernel
# benchmarks' predicted speedups all consult this table.
ARA_FLOP_PER_CYCLE_PER_LANE = {64: 2, 32: 4, 16: 8, 8: 16}

# SEW (bits) <-> numpy/jax dtype name used by the vector engines. SEW=8
# is the integer lane (no FP8 format): int8 two's complement.
SEW_TO_DTYPE = {64: "float64", 32: "float32", 16: "float16", 8: "int8"}
DTYPE_TO_SEW = {"float64": 64, "float32": 32, "float16": 16,
                "bfloat16": 16, "int8": 8}


def dtype_for_sew(sew: int):
    """Element dtype the engines execute at for a given SEW."""
    return jnp.dtype(SEW_TO_DTYPE[sew])


def sew_for_dtype(dtype) -> int:
    """Datapath element width (bits) a dtype occupies on Ara's lanes."""
    return DTYPE_TO_SEW[jnp.dtype(dtype).name]


def ara_speedup_vs_dp(sew: int) -> float:
    """Paper §III-E4 prediction: throughput gain vs the 64-bit datapath."""
    return (ARA_FLOP_PER_CYCLE_PER_LANE[sew]
            / ARA_FLOP_PER_CYCLE_PER_LANE[64])


def issue_amortization(vl: int, lanes: int, sew: int = 64, lmul: int = 1,
                       issue_interval: float = 5.0) -> float:
    """§IV in closed form: FPU-busy cycles of one grouped vector FMA per
    issue slot it consumes. >= 1 means the 5-cycle issue interval is fully
    hidden; register grouping multiplies the numerator by LMUL, which is
    why Ara2 adds it for short-vector workloads."""
    chain = (lmul * vl / lanes) / (64 // sew)   # busy cycles per insn
    return chain / issue_interval


@dataclasses.dataclass(frozen=True)
class Policy:
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    accum_dtype: str = "float32"
    cache_dtype: str = "bfloat16"
    lmul: int = 1                # register grouping the Ara analogue uses;
                                 # kernels scale block shapes by it
    attn_bq: int | None = None   # flash-attention q/kv block shapes;
    attn_bk: int | None = None   # None takes the kernel's shape rule

    def peak_flops(self) -> float:
        return PEAKS_FLOPS[self.compute_dtype]

    @property
    def sew(self) -> int:
        """Ara element width equivalent of the compute dtype."""
        return sew_for_dtype(self.compute_dtype)

    def ara_peak_flop_per_cycle(self, lanes: int) -> int:
        """Ara-side peak at this policy's compute width."""
        return lanes * ARA_FLOP_PER_CYCLE_PER_LANE[self.sew]

    def ara_speedup(self) -> float:
        return ara_speedup_vs_dp(self.sew)

    def issue_amortization(self, vl: int, lanes: int,
                           issue_interval: float = 5.0) -> float:
        """Chain length per issue slot at this policy's SEW and LMUL."""
        return issue_amortization(vl, lanes, self.sew, self.lmul,
                                  issue_interval)

    def cast_params(self, tree):
        import jax
        dt = jnp.dtype(self.compute_dtype)
        return jax.tree_util.tree_map(
            lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating)
            else x, tree)


def bytes_per_element(dtype: str) -> int:
    return jnp.dtype(dtype).itemsize


def speedup_vs_fp32(dtype: str) -> float:
    return PEAKS_FLOPS[dtype] / PEAKS_FLOPS["float32"]
