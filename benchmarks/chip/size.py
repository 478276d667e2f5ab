#!/usr/bin/env python3
"""Size the cells' device memory by compiling for a described v5e, with
no chip; the numbers chosen go into the traffic files by hand.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/size.py

The TPU compiler is installed here and compiles for a chip that is
described, not attached; ``memory_analysis`` of the compiled program
gives the device bytes it plans (arguments + outputs - aliased +
temporaries + code), including the TPU's tiled layouts.  The budget is
``SHARE`` of the ``bytes_limit`` a v5e reports.

* Training: the step of ``repro.train.train_step`` at each global batch
  of the mix's ``size_candidates``; the largest that fits is chosen.
* Serving: the paged decode step at two pool sizes gives the planned
  bytes of one page; the pool is the most pages that fit, capped at the
  engine's default (1 + slots x pages per request), and the slots are
  halved while the pool cannot give every slot a page.  One prefill
  chunk is compiled too.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))

SHARE = 0.9
#: ``memory_stats()["bytes_limit"]`` of one TPU v5 lite chip
BYTES_LIMIT = {"TPU v5 lite": 16_909_336_064}


def planned(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes
            + ma.generated_code_size_in_bytes)


def described_device():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _on(dev, tree):
    import jax
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=dev), tree)


def size_train(config, traffic, dev, budget) -> None:
    import jax
    import jax.numpy as jnp
    from drivers_common import program_config
    from repro.train import train_step as TS
    cfg = program_config(config)
    o = traffic["optimizer"]
    state = _on(dev, jax.eval_shape(
        lambda: TS.init_state(jax.random.PRNGKey(0), cfg)))
    step = jax.jit(TS.make_train_step(cfg, peak_lr=o["peak_lr"],
                                      total_steps=o["total_steps"]),
                   donate_argnums=(0,))
    for gb in traffic["size_candidates"]:
        batch = {k: jax.ShapeDtypeStruct((gb, traffic["seq_len"]),
                                         jnp.int32, sharding=dev)
                 for k in ("tokens", "labels")}
        t0 = time.perf_counter()
        need = planned(step.lower(state, batch).compile())
        print(f"train {config['name']} seq {traffic['seq_len']} batch {gb}:"
              f" plans {need / 2**30:.3f} GiB of {budget / 2**30:.3f} GiB "
              f"({'fits' if need <= budget else 'does not fit'}; compile "
              f"{time.perf_counter() - t0:.1f}s)", flush=True)


def size_serve(config, traffic, dev, budget) -> None:
    import jax
    import jax.numpy as jnp
    from drivers_common import program_config
    from repro.models import transformer as T
    cfg = program_config(config)
    ps, slots = traffic["page_size"], traffic["slots"]
    max_pages = -(-traffic["max_len"] // ps)
    params = _on(dev, jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg)))
    step = jax.jit(lambda p, tok, cache: T.decode_step(p, cfg, tok, cache),
                   donate_argnums=(2,))

    def cache(n_pages, n_slots):
        return _on(dev, jax.eval_shape(lambda: T.init_paged_cache(
            cfg, n_slots, n_pages, ps, max_pages)))

    def plan(n_pages, n_slots):
        tok = jax.ShapeDtypeStruct((n_slots, 1), jnp.int32, sharding=dev)
        return planned(step.lower(params, tok, cache(n_pages, n_slots))
                       .compile())

    raw = (2 * cfg.n_layers * ps * cfg.n_kv_heads * cfg.hd
           * jnp.dtype(cfg.dtype).itemsize)
    while True:
        a, b = plan(9, slots), plan(41, slots)
        per_page = (b - a) / 32
        fit = int(9 + (budget - a) // per_page)
        cap = 1 + slots * max_pages
        print(f"serve {config['name']} slots {slots}: decode step plans "
              f"{a / 2**30:.3f} GiB at 9 pages; one page {per_page / 2**20:.2f}"
              f" MiB planned, {raw / 2**20:.2f} MiB raw; {fit} pages fit, "
              f"cap {cap} -> pool {min(fit, cap)}", flush=True)
        if min(fit, cap) >= 1 + slots or slots == 1:
            break
        slots //= 2
    chunk = jax.jit(lambda p, toks, c, slot, row: T.prefill_paged_chunk(
        p, cfg, toks, c, slot, row, ps - traffic["prefill_chunk"]),
        donate_argnums=(2,))
    compiled = chunk.lower(
        params, jax.ShapeDtypeStruct((1, traffic["prefill_chunk"]),
                                     jnp.int32, sharding=dev),
        cache(9, slots), jax.ShapeDtypeStruct((), jnp.int32, sharding=dev),
        jax.ShapeDtypeStruct((max_pages,), jnp.int32, sharding=dev)).compile()
    print(f"serve {config['name']}: a prefill chunk compiles, Pallas kernels "
          f"{'tpu_custom_call' in compiled.as_text()}", flush=True)


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_KERNELS"] = "pallas"
    import jax
    import harness
    jax.config.update("jax_enable_compilation_cache", False)
    dev = described_device()
    budget = SHARE * BYTES_LIMIT["TPU v5 lite"]
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        found = harness.resolve(bench, w["name"])
        fn = size_train if found["traffic"]["driver"] == "train" \
            else size_serve
        fn(found["config"], found["traffic"], dev, budget)
    return 0


if __name__ == "__main__":
    sys.exit(main())
