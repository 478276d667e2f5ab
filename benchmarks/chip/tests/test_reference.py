"""The plain reference against the program's model at smoke size, on
the CPU, in float32."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import smoke  # noqa: F401
import reference
import weights
from drivers_common import program_config

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def model():
    from repro.models import transformer as T
    c = json.loads((DATA / "smollm-360m-smoke.json").read_text())
    cfg = program_config(c)
    struct = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    return c, cfg, weights.make(struct, 2**31 + 9)


def program_logits(cfg, w, tokens):
    from repro.models import transformer as T
    from repro.models import layers as L
    h, _ = T.forward(w, cfg, jnp.asarray(tokens)[None], remat=False)
    return np.asarray(L.ops.gemm(h[0], w["lm_head"],
                                 out_dtype=jnp.float32))


@pytest.mark.parametrize("window", [0, 24])
def test_logits_match_program(model, window):
    import dataclasses
    c, cfg, w = model
    c = dict(c, sliding_window=window)
    cfg = dataclasses.replace(cfg, window=window)
    toks = np.random.default_rng(0).integers(0, c["vocab_size"], 40)
    want = program_logits(cfg, w, toks)
    got = reference.logits(c, w, toks, pad_to=16)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_window_masks(model):
    c, _, w = model
    toks = np.random.default_rng(1).integers(0, c["vocab_size"], 40)
    full = reference.logits(c, w, toks)
    win = reference.logits(dict(c, sliding_window=8), w, toks)
    np.testing.assert_allclose(win[:8], full[:8], rtol=1e-5, atol=1e-5)
    assert np.abs(win[20:] - full[20:]).max() > 1e-3


def test_served_gap_reads_zero_for_its_own_argmax(model):
    c, _, w = model
    prompt = np.random.default_rng(2).integers(0, c["vocab_size"], 12)
    seq = list(prompt)
    for _ in range(6):
        seq.append(int(reference.logits(c, w, np.asarray(seq))[-1]
                       .argmax()))
    served = np.asarray(seq[len(prompt):], np.int32)
    assert reference.served_gap(c, w, prompt.astype(np.int32),
                                served) == 0.0
    bad = served.copy()
    bad[3] = (bad[3] + 1) % c["vocab_size"]
    assert reference.served_gap(c, w, prompt.astype(np.int32), bad) > 0.0


def test_fp8_rounds(model):
    x = jnp.linspace(-3.0, 3.0, 1001)
    q = reference._round_fp8(x)
    err = np.abs(np.asarray(q - x))
    # 3 mantissa bits: within half a step, 2**-4 of the value (subnormals
    # near zero are coarser in relative terms, finer in absolute ones)
    assert np.all(err <= np.abs(np.asarray(x)) / 16 + 2e-3)
    assert err.max() > 1e-2
