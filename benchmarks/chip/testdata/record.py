#!/usr/bin/env python3
"""Record the small trace in this directory, on the chip.

    python3 benchmarks/chip/testdata/record.py <out-dir>

Traces three calls of the program's planned GEMM and one flash-attention
forward at small shapes, with the Python tracer on, and copies the
``.xplane.pb`` to ``<out-dir>/small.xplane.pb``.  It also prints every
plane and line of the trace and the first events of each device line
with their statistics, so that the names the reduction matches can be
checked by hand.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[2] / "src"))
sys.path.insert(0, str(HERE.parent))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from repro import ops
    import trace

    k = jax.random.split(jax.random.PRNGKey(0), 5)
    a = jax.random.normal(k[0], (512, 1024), jnp.bfloat16)
    b = jax.random.normal(k[1], (1024, 768), jnp.bfloat16)
    q = jax.random.normal(k[2], (1, 512, 4, 128), jnp.bfloat16)
    kk = jax.random.normal(k[3], (1, 512, 4, 128), jnp.bfloat16)
    v = jax.random.normal(k[4], (1, 512, 4, 128), jnp.bfloat16)
    gemm = jax.jit(lambda x, y: ops.gemm(x, y))
    attn = jax.jit(lambda x, y, z: ops.attention(x, y, z, causal=True))
    jax.block_until_ready((gemm(a, b), attn(q, kk, v)))
    log_dir = tempfile.mkdtemp(prefix="bench_record_")
    tr = trace.Tracer(log_dir)
    tr.start()
    for _ in range(3):
        jax.block_until_ready(gemm(a, b))
    jax.block_until_ready(attn(q, kk, v))
    tr.stop()
    path = trace.find_xplane(log_dir)
    Path(out).mkdir(parents=True, exist_ok=True)
    shutil.copy(path, Path(out) / "small.xplane.pb")
    prof = trace.load(path)
    for plane in prof.planes:
        print("PLANE", plane.name, [ln.name for ln in plane.lines])
        if not plane.name.startswith("/device"):
            continue
        for line in plane.lines:
            for ev in list(line.events)[:6]:
                print("  ", line.name, "|", ev.name, ev.duration_ns,
                      [(s, v) for s, v in ev.stats][:12])
    print(trace.reduce(prof))
    shutil.rmtree(log_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
