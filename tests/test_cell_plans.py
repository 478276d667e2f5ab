"""The plans the benchmark's two training cells run, pinned: every GEMM
and attention plan the train step of ``smollm-360m.train-2k`` and of
``h2o-danube-3-4b.train-2x2`` resolves under Pallas dispatch, with its
kernel, tile or blocks, modeled VMEM and fallback.  A change to the
planner or the cost model that moves any decision of either cell fails
here, on the CPU, before it reaches the chip.

Each cell's step is traced with ``jax.eval_shape`` at its full size in
a subprocess (the danube stage on a (data 2, model 2) mesh of 4 host
devices): the planners run, nothing is compiled or executed.  Under the
mesh each GEMM is planned at its global shape and again at the shape of
one device's block, so danube lists both."""

import functools
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

#: cell -> (registry config, seq_len, global batch, data, model)
CELLS = {
    "smollm": ("smollm-360m", 2048, 16, 1, 1),
    "danube": ("h2o-danube-3-4b-12l", 4096, 8, 2, 2),
}

_TRACE_SCRIPT = r"""
import json, sys
import jax, jax.numpy as jnp
from repro import ops
from repro.configs.base import get_config
from repro.dist import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.train import train_step as TS

name, seq, batch, data, model = sys.argv[1], *map(int, sys.argv[2:])
cfg = get_config(name)
step = TS.make_train_step(cfg)
tokens = {k: jax.ShapeDtypeStruct((batch, seq), jnp.int32)
          for k in ("tokens", "labels")}
with shd.use_mesh(make_host_mesh(data=data, model=model)):
    state = jax.eval_shape(lambda: TS.init_state(jax.random.PRNGKey(0),
                                                 cfg))
    jax.eval_shape(step, state, tokens)
plans = {}
for p in ops.plans():
    s, t = p.spec, p.tile
    kern = ("gemm_gated" if s.gated else "gemm_grouped" if s.grouped
            else "gemm_" + t.strategy)
    plans["gemm %s %dx%dx%d" % (s.key, p.m, p.k, p.n)] = [
        kern, t.strategy, [t.bm, t.bk, t.bn], p.vmem_bytes,
        p.fallback_reason]
for p in ops.attn_plans():
    bw = p.bwd
    plans["attn %s %s" % (p.spec.key, p.shape_key)] = [
        p.kernel, [p.bq, p.bkv], list(p.blocks),
        [bw.kernel, [bw.bq, bw.bkv], list(bw.blocks)]]
print("PLANS " + json.dumps(plans))
"""


@functools.cache
def traced(cell: str) -> dict:
    """Every plan ``cell``'s train step resolves, by plan key."""
    name, seq, batch, data, model = CELLS[cell]
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               REPRO_KERNELS="pallas")
    if data * model > 1:
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                            f"{data * model}")
    r = subprocess.run(
        [sys.executable, "-c", _TRACE_SCRIPT, name,
         *map(str, (seq, batch, data, model))],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = next(x for x in r.stdout.splitlines()
                if x.startswith("PLANS "))
    return json.loads(line[len("PLANS "):])


#: (cell, spec key, (m, k, n), kernel, strategy, (bm, bk, bn), modeled
#: VMEM bytes, fallback reason) of every GEMM plan
GEMM_PLANS = [
    ("smollm", "bfloat16xbfloat16", (32768, 960, 960),
     "gemm_aie", "aie", (2048, 128, 1024), 18350080, None),
    ("smollm", "bfloat16xbfloat16", (32768, 960, 320),
     "gemm_tb", "tb", (2048, 1024, 128), 13107200, None),
    ("smollm", "bfloat16xbfloat16:res", (32768, 960, 960),
     "gemm_aie", "aie", (2048, 128, 1024), 26738688, None),
    ("smollm", "bfloat16xbfloat16:gated:silu", (32768, 960, 2560),
     "gemm_gated", "aie", (2048, 128, 512), 18350080, None),
    ("smollm", "bfloat16xbfloat16:res", (32768, 2560, 960),
     "gemm_aie", "aie", (2048, 128, 1024), 26738688, None),
    ("smollm", "bfloat16xbfloat16->float32", (4096, 960, 49152),
     "gemm_aie", "aie", (2048, 128, 2048), 52428800, None),
    ("smollm", "bfloat16xbfloat16->bfloat16", (4096, 49152, 960),
     "gemm_aie", "aie", (2048, 128, 1024), 18350080, None),
    ("smollm", "bfloat16xbfloat16->bfloat16", (960, 4096, 49152),
     "gemm_aie", "aie", (512, 128, 2048), 9699328, None),
    ("smollm", "bfloat16xbfloat16->bfloat16", (32768, 960, 2560),
     "gemm_aie", "aie", (2048, 128, 512), 9699328, None),
    ("smollm", "bfloat16xbfloat16->bfloat16", (2560, 32768, 960),
     "gemm_aie", "aie", (512, 128, 1024), 4980736, None),
    ("smollm", "bfloat16xbfloat16->float32", (32768, 960, 2560),
     "gemm_aie", "aie", (2048, 128, 512), 13893632, None),
    ("smollm", "bfloat16xbfloat16->bfloat16", (32768, 2560, 960),
     "gemm_aie", "aie", (2048, 128, 1024), 18350080, None),
    ("smollm", "bfloat16xbfloat16->bfloat16", (960, 32768, 2560),
     "gemm_aie", "aie", (512, 128, 512), 2621440, None),
    ("smollm", "bfloat16xbfloat16->bfloat16", (32768, 960, 960),
     "gemm_aie", "aie", (2048, 128, 1024), 18350080, None),
    ("smollm", "bfloat16xbfloat16->bfloat16", (960, 32768, 960),
     "gemm_aie", "aie", (512, 128, 1024), 4980736, None),
    ("smollm", "bfloat16xbfloat16->bfloat16", (32768, 320, 960),
     "gemm_aie", "aie", (2048, 128, 1024), 18350080, None),
    ("smollm", "bfloat16xbfloat16->bfloat16", (960, 32768, 320),
     "gemm_tb", "tb", (512, 2048, 128), 6291456, None),
    ("danube", "bfloat16xbfloat16", (32768, 3840, 3840),
     "gemm_aie", "aie", (2048, 128, 2048), 35651584, None),
    ("danube", "bfloat16xbfloat16", (16384, 3840, 1920),
     "gemm_aie", "aie", (2048, 128, 1024), 18350080, None),
    ("danube", "bfloat16xbfloat16", (32768, 3840, 960),
     "gemm_aie", "aie", (2048, 128, 1024), 18350080, None),
    ("danube", "bfloat16xbfloat16", (16384, 3840, 480),
     "gemm_aie", "aie", (2048, 128, 512), 9699328, None),
    ("danube", "bfloat16xbfloat16:res", (32768, 3840, 3840),
     "gemm_aie", "aie", (2048, 128, 2048), 52428800, None),
    ("danube", "bfloat16xbfloat16:res", (16384, 3840, 1920),
     "gemm_aie", "aie", (2048, 128, 1024), 26738688, None),
    ("danube", "bfloat16xbfloat16:gated:silu", (32768, 3840, 10240),
     "gemm_gated", "aie", (2048, 128, 2048), 70254592, None),
    ("danube", "bfloat16xbfloat16:gated:silu", (16384, 3840, 5120),
     "gemm_gated", "aie", (2048, 128, 1024), 35651584, None),
    ("danube", "bfloat16xbfloat16:res", (32768, 10240, 3840),
     "gemm_tb", "tb", (2048, 2048, 128), 23068672, None),
    ("danube", "bfloat16xbfloat16:res", (16384, 10240, 1920),
     "gemm_tb", "tb", (2048, 2048, 128), 23068672, None),
    ("danube", "bfloat16xbfloat16->float32", (4096, 3840, 32000),
     "gemm_aie", "aie", (2048, 128, 512), 13893632, None),
    ("danube", "bfloat16xbfloat16->float32", (2048, 3840, 16000),
     "gemm_aie", "aie", (2048, 128, 2048), 52428800, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (4096, 32000, 3840),
     "gemm_tb", "tb", (2048, 2048, 128), 22020096, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (2048, 32000, 1920),
     "gemm_tb", "tb", (2048, 2048, 128), 22020096, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (3840, 4096, 32000),
     "gemm_tb", "tb", (2048, 2048, 128), 22020096, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (1920, 4096, 16000),
     "gemm_tb", "tb", (1024, 2048, 128), 11534336, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (32768, 3840, 10240),
     "gemm_aie", "aie", (2048, 128, 2048), 35651584, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (16384, 3840, 5120),
     "gemm_aie", "aie", (2048, 128, 1024), 18350080, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (10240, 32768, 3840),
     "gemm_tb", "tb", (2048, 2048, 128), 22020096, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (5120, 32768, 1920),
     "gemm_tb", "tb", (1024, 2048, 128), 11534336, None),
    ("danube", "bfloat16xbfloat16->float32", (32768, 3840, 10240),
     "gemm_aie", "aie", (2048, 128, 2048), 52428800, None),
    ("danube", "bfloat16xbfloat16->float32", (16384, 3840, 5120),
     "gemm_aie", "aie", (2048, 128, 1024), 26738688, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (32768, 10240, 3840),
     "gemm_tb", "tb", (2048, 2048, 128), 22020096, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (16384, 10240, 1920),
     "gemm_tb", "tb", (2048, 2048, 128), 22020096, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (3840, 32768, 10240),
     "gemm_aie", "aie", (256, 128, 2048), 5373952, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (1920, 32768, 5120),
     "gemm_aie", "aie", (1024, 128, 1024), 9437184, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (32768, 3840, 3840),
     "gemm_aie", "aie", (2048, 128, 2048), 35651584, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (16384, 3840, 1920),
     "gemm_aie", "aie", (2048, 128, 1024), 18350080, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (3840, 32768, 3840),
     "gemm_tb", "tb", (2048, 2048, 128), 22020096, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (1920, 32768, 1920),
     "gemm_tb", "tb", (1024, 2048, 128), 11534336, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (32768, 960, 3840),
     "gemm_tb", "tb", (2048, 1024, 128), 13107200, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (16384, 960, 1920),
     "gemm_tb", "tb", (2048, 1024, 128), 13107200, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (3840, 32768, 960),
     "gemm_aie", "aie", (2048, 128, 1024), 18350080, None),
    ("danube", "bfloat16xbfloat16->bfloat16", (1920, 32768, 480),
     "gemm_aie", "aie", (1024, 128, 512), 4980736, None),
]

#: (cell, spec key, shape key, kernel, (bq, bkv), (computed, of all)
#: forward blocks, (backward kernel, (bq, bkv), blocks)) of every
#: attention plan
ATTN_PLANS = [
    ("smollm", "attn|prefill:causal:g3:bfloat16xbfloat16",
     "b16x2048x2048xh15/5xd64",
     "flash_attention", (512, 512), (10, 16),
     ("flash_attention_bwd", (512, 512), (10, 16))),
    ("danube", "attn|prefill:causal:w4096:g4:bfloat16xbfloat16",
     "b8x4096x4096xh32/8xd120",
     "flash_attention", (512, 512), (36, 64),
     ("flash_attention_bwd", (512, 512), (36, 64))),
]


def _cases():
    for cell, key, (m, k, n), kern, strat, tile, vmem, fb in GEMM_PLANS:
        yield pytest.param(
            cell, f"gemm {key} {m}x{k}x{n}",
            [kern, strat, list(tile), vmem, fb],
            id=f"{cell}-gemm-{key}-{m}x{k}x{n}")
    for cell, key, shape, kern, blocks, share, bwd in ATTN_PLANS:
        bkern, bblocks, bshare = bwd
        yield pytest.param(
            cell, f"attn {key} {shape}",
            [kern, list(blocks), list(share),
             [bkern, list(bblocks), list(bshare)]],
            id=f"{cell}-{key}-{shape}")


CASES = list(_cases())


@pytest.mark.parametrize("cell,key,want", CASES)
def test_cell_plan_is_pinned(cell, key, want):
    plans = traced(cell)
    assert len(plans) == sum(c.values[0] == cell for c in CASES), \
        sorted(plans)
    assert key in plans, sorted(plans)
    assert plans[key] == want
