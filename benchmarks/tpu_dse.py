"""The adapted technique: reuse-maximizing tiling DSE on TPU v5e.

Runs the paper's IP/DSE formulation (lifted onto the HBM->VMEM hierarchy)
over the GEMM problems the assigned architectures actually produce —
per-arch projection shapes at the train_4k per-device scale plus the
paper's own square sweep — and reports, per problem, the winning
(strategy, bm, bk, bn), modeled arithmetic intensity, HBM traffic and
the roofline bound, exactly as Tables III/IV report (design, reuse, BW,
throughput) for the FPGAs.
"""

from __future__ import annotations

from repro.configs.base import ARCH_IDS, get_config
from repro.core import dse
from repro.core.hardware import TPU_V5E
from repro.core.memory_model import fits_vmem_bytes
from repro.core.tiling import GemmProblem

# per-device M for train_4k on the 16x16 mesh: (256/16) rows x 4096 seq
M_TRAIN = 16 * 4096


def arch_problems():
    """The dominant per-device projection GEMMs per architecture.

    Dense archs: d_ff/heads shard over the 16-way 'model' axis (TP).
    MoE archs: experts shard over 'model' (EP), so the per-expert GEMM
    keeps the full d_ff but sees only top_k/n_experts of the tokens —
    these come out *memory-bound* (skinny M), which is exactly the
    expert-dispatch bottleneck the §Perf pass attacks.
    """
    out = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        tp = 16
        if cfg.n_experts:
            m_exp = max(M_TRAIN * cfg.top_k // cfg.n_experts, 8)
            out.append((f"{arch}:expert_ffn",
                        GemmProblem(m_exp, cfg.d_model, cfg.d_ff)))
        else:
            d_ff = cfg.d_ff if cfg.d_ff else cfg.d_model * 2
            out.append((f"{arch}:ffn_up",
                        GemmProblem(M_TRAIN, cfg.d_model,
                                    max(d_ff // tp, 128))))
        out.append((f"{arch}:attn_qkv",
                    GemmProblem(M_TRAIN, cfg.d_model,
                                max(cfg.n_heads * cfg.hd // tp, 128))))
    return out


def square_problems():
    return [(f"square_{s}", GemmProblem(s, s, s, "int8", "int8", "int32"))
            for s in (512, 2048, 8192)]


def run(report) -> None:
    chip = TPU_V5E
    for name, p in arch_problems() + square_problems():
        designs = dse.solve(p, chip, top=3)
        best = designs[0]
        t = best.tile
        # sanity gates: feasible, MXU-aligned, VMEM within budget,
        # and for the big square problems the DSE must find a
        # compute-bound tiling (arithmetic intensity above the ridge)
        ridge = (chip.peak_int8_ops if p.in_dtype == "int8"
                 else chip.peak_bf16_flops) / chip.hbm_bw
        ok = (t.mxu_aligned(chip)
              and fits_vmem_bytes(best.vmem_bytes, chip))
        if name.startswith("square") and p.m >= 2048:
            # large square GEMMs must tile compute-bound (paper regime)
            ok = ok and best.traffic.bound == "compute"
        report.row(
            "tpu_dse", name,
            tile=f"{t.strategy} {t.bm}x{t.bk}x{t.bn}",
            vmem=f"{best.vmem_bytes/2**20:.1f}MiB eff={best.vmem_eff:.2f}",
            traffic=f"AI={best.traffic.arithmetic_intensity:.0f} "
                    f"(ridge {ridge:.0f}) bound={best.traffic.bound}",
            ok=ok)


if __name__ == "__main__":
    from benchmarks.run import Report
    rep = Report()
    run(rep)
    rep.print()
