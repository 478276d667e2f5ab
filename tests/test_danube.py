"""h2o-danube-3-4b: the 12-layer stage the benchmark trains, its
configuration file, and training on a 2x2 mesh against the plain float32
reference (``benchmarks/chip/reference.py``).

The training cases run the benchmark's own driver of the cell
(``drivers/train_sharded.py``: the program's ``launch.train.build`` step,
weights from the seed, three checked steps, then the reference laid out
for the mesh, ``reference_sharded.py``, on the same weights and batches;
it matches ``reference.py`` to round-off, checked here too) in a subprocess with
4 host devices and Pallas kernels in interpret mode.  Each model runs on
a (data 2, model 2) mesh and on one device.  Two models: the program's
smoke reduction, and one at head_dim 120 with a GQA group of 4 (8 query
and 2 kv heads, so a model shard holds 4 and 1), whose flash kernels pad
the head dim to 128 lanes.  The sequence of 128 puts attention on the
flash kernels (forward and backward).

Tolerances: both sides compute in float32, so the gaps are round-off of
the order of 1e-6; they are held to 1e-4 (gradient) and 1e-3 (change
after three AdamW steps, whose normalized update magnifies round-off in
leaves with small gradients).  A model shard that attended with another
shard's kv heads, or a gradient left unreduced across a mesh axis,
reads of the order of 1.
"""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs.base import get_config
from repro.models import transformer as T

ROOT = Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmarks" / "chip"

GRAD_GAP = 1e-4
CHANGE_GAP = 1e-3


def test_stage_entry_is_the_full_model_at_12_layers():
    full = get_config("h2o-danube-3-4b")
    stage = get_config("h2o-danube-3-4b-12l")
    assert stage.n_layers == 12 and full.n_layers == 24
    assert dataclasses.replace(stage, name=full.name, n_layers=24) == full
    assert (stage.d_model, stage.hd, stage.n_heads, stage.n_kv_heads,
            stage.d_ff, stage.vocab) == (3840, 120, 32, 8, 10240, 32000)


def test_configuration_file_passes_program_config():
    sys.path.insert(0, str(CHIP))
    try:
        from drivers_common import program_config
    finally:
        sys.path.remove(str(CHIP))
    c = json.loads((CHIP / "configs" / "h2o-danube-3-4b.json").read_text())
    cfg = program_config(c)
    assert cfg is get_config("h2o-danube-3-4b-12l")
    # the 12-layer stage holds 2,103,801,600 weights, norm scales
    # included: 25.2 GB of training state, more than a chip holds
    struct = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    assert sum(x.size for x in jax.tree.leaves(struct)) == 2_103_801_600
    wrong = dict(c, head_dim=128)
    with pytest.raises(RuntimeError):
        program_config(wrong)


_SCRIPT = r"""
import argparse, contextlib, dataclasses, io, json, sys, time
sys.path[:0] = ["src", "benchmarks/chip"]
import jax
import harness
from repro.configs.base import get_smoke_config, register

smoke = get_smoke_config("h2o-danube-3-4b")
register(dataclasses.replace(smoke, name="h2o-danube-hd120-test",
                             d_model=240, n_heads=8, n_kv_heads=2,
                             head_dim=120, d_ff=256))
TRAFFIC = json.loads(open("benchmarks/chip/traffic/train-4k-2x2.json").read())
TRAFFIC.update(seq_len=128, global_batch=4, checked_steps=3, trace_steps=1)
BENCH = json.loads(open("BENCHMARK.json").read())
LIMITS = {"grad_gap": %(grad)r, "change_gap": %(change)r}
out = {}
for reg in ("h2o-danube-3-4b-smoke", "h2o-danube-hd120-test"):
    from repro.configs.base import get_config
    cfg = get_config(reg)
    for data, model in ((2, 2), (1, 1)):
        c = {"name": reg, "registry": reg, "reference": "reference_sharded",
             "dtype": "float32", "mesh": {"data": data, "model": model},
             "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
             "num_attention_heads": cfg.n_heads,
             "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.hd,
             "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab,
             "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
             "sliding_window": cfg.window}
        found = {"cell": {"name": f"{reg}.{data}x{model}", "config": reg,
                          "traffic": "test", "chips": data * model},
                 "config": c, "traffic": TRAFFIC,
                 "limits": {"checks": LIMITS}}
        args = argparse.Namespace(workload=found["cell"]["name"],
                                  seed=2**33 + 5, seconds=0.1, trace=0,
                                  rate=None, control=0)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = harness.run_resolved(BENCH, found, args,
                                      time.perf_counter(),
                                      device={"platform": "cpu",
                                              "kind": "cpu",
                                              "count": data * model})
        line = json.loads(buf.getvalue().strip().splitlines()[-1])
        out[f"{reg}@{data}x{model}"] = {"rc": rc, **line}
print("RESULTS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def trained():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["REPRO_KERNELS"] = "interpret"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c",
         _SCRIPT % {"grad": GRAD_GAP, "change": CHANGE_GAP}],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    line = next(x for x in r.stdout.splitlines()
                if x.startswith("RESULTS "))
    return json.loads(line[len("RESULTS "):])


@pytest.mark.parametrize("case", [
    "h2o-danube-3-4b-smoke@2x2", "h2o-danube-3-4b-smoke@1x1",
    "h2o-danube-hd120-test@2x2", "h2o-danube-hd120-test@1x1"])
def test_trains_like_the_reference(trained, case):
    got = trained[case]
    assert got["rc"] == 0
    checks = got["checks"]
    assert checks["grad_gap"]["value"] <= GRAD_GAP, checks
    assert checks["change_gap"]["value"] <= CHANGE_GAP, checks
    assert got["correct"] is True
    assert got["failed"] == 0 and got["attempted"] >= 1


@pytest.mark.parametrize("rows", [None, (0, 2)])
def test_sharded_reference_matches_the_reference(rows):
    """``reference_sharded.train_steps`` (layer by layer, attention in
    query blocks) against ``reference.train_steps`` on one device, at
    the smoke size with a window and several query blocks: the same
    float32 mathematics, so round-off apart (gaps of the order of 1e-7;
    held to 1e-5)."""
    sys.path.insert(0, str(CHIP))
    try:
        import compare
        import reference
        import reference_sharded
        import weights
        from drivers_common import program_config
        from harness import load_module
    finally:
        sys.path.remove(str(CHIP))
    c = json.loads((CHIP / "tests" / "data" / "smollm-360m-smoke.json")
                   .read_text())
    cfg = dataclasses.replace(program_config(c), window=24)
    c = dict(c, sliding_window=24)
    struct = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    w = weights.make(struct, 2**33 + 3)
    gen = load_module(CHIP / "traffic" / "train_batch.py")
    p = json.loads((CHIP / "traffic" / "train-4k-2x2.json").read_text())
    p.update(seq_len=64, global_batch=3)
    batches = [gen.batch(p, 7, i, c["vocab_size"]) for i in range(3)]
    o = p["optimizer"]
    old = reference_sharded.Q_BLOCK
    reference_sharded.Q_BLOCK = 16
    try:
        got = reference_sharded.train_steps(c, w, batches, o, rows=rows)
    finally:
        reference_sharded.Q_BLOCK = old
    want = reference.train_steps(c, w, batches, o, rows=rows)
    gaps = compare.train_gaps(got, want)
    assert gaps["grad_gap"] < 1e-5 and gaps["change_gap"] < 1e-5, gaps
    assert compare.loss_gap(got, want) < 1e-5
