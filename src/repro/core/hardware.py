"""Hardware constant sheets.

Three devices appear in this framework:

* ``TPU_V5E`` — the *target* device for the adapted framework (kernels,
  sharding, roofline). Peaks are the published ones (Google Cloud
  documentation, "TPU v5e"): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of
  HBM at 819 GB/s.  :func:`chip_for_kind` maps a device's
  ``device_kind`` to its constants and refuses a kind it does not know.
* ``VERSAL_VC1902`` and ``STRATIX_NX2100`` — the paper's devices (Table I),
  used by :mod:`repro.core.paper_model` to reproduce the paper's analytical
  results (Tables II–IV) faithfully.
"""

from __future__ import annotations

import dataclasses

KiB = 1024
MiB = 1024 * KiB
GiB = 1024 * MiB


@dataclasses.dataclass(frozen=True)
class TPUChip:
    """A TPU chip model used for roofline + DSE constraints."""

    name: str
    peak_bf16_flops: float          # FLOP/s
    peak_int8_ops: float            # OP/s
    hbm_bytes: int                  # HBM capacity per chip
    hbm_bw: float                   # bytes/s
    vmem_bytes: int                 # VMEM scratchpad per core
    vmem_limit_bytes: int           # scoped VMEM one kernel may ask for
    ici_link_bw: float              # bytes/s per link, per direction
    ici_links: int                  # torus links per chip
    dcn_bw: float                   # bytes/s per chip for pod-to-pod traffic
    mxu_dim: int = 128              # systolic array edge
    sublanes: int = 8               # fp32 sublane count; bf16=16, int8=32
    lane: int = 128

    def sublane(self, dtype_bytes: int) -> int:
        """Minimum tile in the second-to-last dim for a dtype."""
        return self.sublanes * max(1, 4 // dtype_bytes)

    @property
    def peak_flops(self) -> float:
        return self.peak_bf16_flops


TPU_V5E = TPUChip(
    name="tpu_v5e",
    peak_bf16_flops=197e12,
    peak_int8_ops=393e12,
    hbm_bytes=16 * GiB,
    hbm_bw=819e9,
    vmem_bytes=128 * MiB,
    # v5e compiles take a requested scoped limit up to the 128 MiB of
    # VMEM, but refuse a kernel whose allocations pass 128 MiB in all (an
    # aie tile modeled at 120 MiB allocated 129.85 MiB); 100 MiB leaves
    # the rest to allocations the model does not bill.
    vmem_limit_bytes=100 * MiB,
    ici_link_bw=50e9,
    ici_links=4,            # 2D torus on v5e: 4 links
    dcn_bw=25e9,            # conservative per-chip share of pod-to-pod DCN
)


#: chip constants keyed by ``jax.Device.device_kind``
CHIPS = {"TPU v5 lite": TPU_V5E}


def chip_for_kind(device_kind: str) -> TPUChip:
    """The constants of a TPU by its ``device_kind``; an unknown kind is
    an error, never a default."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no chip constants for device kind {device_kind!r}; known: "
            f"{sorted(CHIPS)}") from None


@dataclasses.dataclass(frozen=True)
class FPGADevice:
    """Paper Table I rows (only the fields the analytical models consume)."""

    name: str
    bram_36k: int            # Versal: 36Kb BRAM count; Stratix: M20K count
    uram_288k: int           # Versal only (0 for Stratix)
    onchip_mem_bytes: float
    peak_tops_int8: float
    peak_dram_bw: float      # bytes/s
    peak_power_w: float
    compute_units: int       # AIE cores (Versal) / Tensor Blocks (Stratix)


# Versal VC1902: 967 36Kb BRAMs + 463 URAMs (AM007); paper quotes utilization
# percentages that imply B36K=967 and U288K=463: e.g. Table II: 780/81%≈963,
# 408/88%≈464, 912/94%≈970, 400/86%≈465 -> (967, 463) matches all rows.
VERSAL_VC1902 = FPGADevice(
    name="versal_vc1902",
    bram_36k=967,
    uram_288k=463,
    onchip_mem_bytes=20.5e6 + 12.5e6,     # PL + AIE memory (Table I)
    peak_tops_int8=135e12,
    peak_dram_bw=102.4e9,
    peak_power_w=165.0,
    compute_units=400,                    # AIE cores
)

# Stratix 10 NX 2100: 6847 M20Ks (paper percentages: 6304/92%≈6852,
# 5840/85%≈6871, 6464/94%≈6877 -> 6847 is the published device count).
STRATIX_NX2100 = FPGADevice(
    name="stratix_nx2100",
    bram_36k=6847,                        # M20K blocks
    uram_288k=0,
    onchip_mem_bytes=16.75e6,
    peak_tops_int8=143e12,
    peak_dram_bw=512e9,
    peak_power_w=125.0,
    compute_units=3960,                   # Tensor Blocks
)


# Versal AIE single-kernel shape used by all MaxEVA solutions in the paper.
AIE_KERNEL_M, AIE_KERNEL_K, AIE_KERNEL_N = 32, 128, 32
AIE_FREQ_HZ = 1.25e9
AIE_KERNEL_EFFICIENCY = 0.95              # paper §V-A: 95% MatMul efficiency
AIE_MACS_PER_CYCLE = 128                  # int8 MACs/cycle/core (128 ops=2*128)

# Stratix TB constants (paper §III-B).
TB_CHAIN = 36                             # TBs per physical chain
TB_DOT = 10                               # dot-product width
TB_LANES = 3                              # parallel dot engines / TB
TB_LOAD_CYCLES = 3                        # cascade loading cycles per TB
TB_CASCADE_CYCLES = 2                     # dot+cascade latency per TB
