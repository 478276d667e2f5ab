"""Plain float32 reference of the dense llama-family decoder.

RMSNorm, rotary embeddings (rotate-half, as the published llama code),
grouped-query attention with causal and sliding-window masks, SwiGLU,
an untied lm head, next-token cross-entropy, global-norm clipping and
AdamW.  Every product runs in float32 under
``jax.default_matmul_precision("highest")``.  It imports nothing of the
program under test: it reads the weights the benchmark made by their
names in the parameter tree, and the sizes from the configuration file.

Departures from the published SmolLM and H2O-Danube3 models, shared
with the program and listed under ``reduced`` in each configuration
file: the lm head is untied, and ``rms_norm_eps`` is the file's value.

It fits at published widths by working in blocks: serving logits are
computed one sequence at a time and one layer at a time (each layer's
weights are cast to float32 only while that layer runs), and a training
step takes one row of the batch at a time, with each layer
rematerialized in the backward pass.

``precision="fp8"`` is the control: the same computation with every
operand of every product rounded to float8_e4m3fn under a per-tensor
scale (straight-through in the backward pass), the step below the
bfloat16 that the configurations state.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8_MAX = 448.0


def _round_fp8(x):
    """x rounded to float8_e4m3fn under one per-tensor scale; the
    gradient passes straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, precision: str):
    x, w = x.astype(F32), w.astype(F32)
    if precision == "fp8":
        x, w = _round_fp8(x), _round_fp8(w)
    return x @ w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, theta):
    """x: (s, h, d), positions 0..s-1, rotate-half convention."""
    s, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs          # (s, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(c: dict, precision: str, x, w):
    """One decoder layer over a whole sequence x: (s, d) float32."""
    s = x.shape[0]
    hq, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    eps = c["rms_norm_eps"]
    h = _rms(x, w["norm1"]["scale"], eps)
    a = w["attn"]
    q = _rope(_mm(h, a["wq"], precision).reshape(s, hq, hd),
              c["rope_theta"])
    k = _rope(_mm(h, a["wk"], precision).reshape(s, hkv, hd),
              c["rope_theta"])
    v = _mm(h, a["wv"], precision).reshape(s, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q * hd ** -0.5, k)
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    mask = ki <= qi
    if c.get("sliding_window"):
        mask &= ki > qi - c["sliding_window"]
    scores = jnp.where(mask[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    x = x + _mm(o.reshape(s, hq * hd), a["wo"], precision)
    h = _rms(x, w["norm2"]["scale"], eps)
    m = w["mlp"]
    g = jax.nn.silu(_mm(h, m["w_gate"], precision)) \
        * _mm(h, m["w_up"], precision)
    return x + _mm(g, m["w_down"], precision)


def _layer_at(c, precision, x, stacked, i):
    w = jax.tree.map(lambda a: a[i], stacked)
    return _layer(c, precision, x, w)


def _head(c, precision, x, final_scale, lm_head):
    return _mm(_rms(x, final_scale, c["rms_norm_eps"]), lm_head, precision)


@functools.lru_cache(maxsize=None)
def _jitted(kind: str, c_items: tuple, precision: str):
    c = dict(c_items)
    if kind == "layer":
        fn = functools.partial(_layer_at, c, precision)
    elif kind == "head":
        fn = functools.partial(_head, c, precision)
    else:
        fn = functools.partial(_row_loss_grad, c, precision)
    return jax.jit(fn)


def _key(c: dict) -> tuple:
    keys = ("num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rms_norm_eps",
            "rope_theta", "sliding_window", "vocab_size", "hidden_size")
    return tuple((k, c.get(k)) for k in keys)


def logits(c: dict, w: dict, tokens: np.ndarray, *,
           precision: str = "f32", pad_to: int = 512) -> np.ndarray:
    """Logits (s, vocab) of one sequence, computed layer by layer.

    The sequence is padded at its end to a multiple of ``pad_to`` so
    that few shapes compile; under the causal mask the padding changes
    no earlier position."""
    s = len(tokens)
    padded = -(-s // pad_to) * pad_to
    toks = np.zeros((padded,), np.int32)
    toks[:s] = tokens
    layer = _jitted("layer", _key(c), precision)
    head = _jitted("head", _key(c), precision)
    stacked = w["layers"]["u0"]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], jnp.asarray(toks), axis=0).astype(F32)
        for i in range(c["num_hidden_layers"]):
            x = layer(x, stacked, jnp.int32(i))
        out = head(x, w["final_norm"]["scale"], w["lm_head"])
    return np.asarray(out[:s])


def served_gap(c: dict, w: dict, prompt: np.ndarray, served: np.ndarray,
               *, precision: str = "f32") -> float:
    """Widest gap, over the served tokens, by which a served token's
    reference logit lies below the reference's best at its position."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    lg = logits(c, w, seq, precision=precision)[len(prompt) - 1:]
    best = lg.max(-1)
    got = lg[np.arange(len(served)), served]
    return float((best - got).max())


def control_gap(c: dict, w: dict, prompt: np.ndarray, served: np.ndarray,
                *, precision: str = "fp8") -> float:
    """The control's reading at the same positions: the float32 gap of
    the token that the lower precision puts first."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    n = len(prompt) - 1
    ref = logits(c, w, seq)[n:]
    low = logits(c, w, seq, precision=precision)[n:]
    pick = low.argmax(-1)
    return float((ref.max(-1) - ref[np.arange(len(pick)), pick]).max())


# ------------------------------------------------------------- training

def _row_loss_grad(c, precision, params, tokens, labels):
    """Summed next-token loss of one row and its gradient."""
    def loss(p):
        x = jnp.take(p["embed"], tokens, axis=0).astype(F32)

        def body(h, lw):
            return jax.checkpoint(
                functools.partial(_layer, c, precision))(h, lw), None

        x, _ = jax.lax.scan(body, x, p["layers"]["u0"])
        lg = _head(c, precision, x, p["final_norm"]["scale"],
                   p["lm_head"])
        logz = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, labels[:, None], -1)[:, 0]
        return jnp.sum(logz - gold)
    return jax.value_and_grad(loss)(params)


def _lr(step: int, o: dict) -> float:
    """Warmup then cosine decay, as the configuration's optimizer states."""
    t, warm, total = float(step), o["warmup_steps"], o["total_steps"]
    if t < warm:
        return o["peak_lr"] * t / max(warm, 1)
    frac = min(max((t - warm) / max(total - warm, 1), 0.0), 1.0)
    return o["peak_lr"] * (o["min_ratio"] + (1 - o["min_ratio"])
                           * 0.5 * (1 + np.cos(np.pi * frac)))


def train_steps(c: dict, w: dict, batches: Sequence[dict], opt: dict, *,
                precision: str = "f32", rows: Sequence[int] = None
                ) -> dict:
    """Run the first ``len(batches)`` AdamW steps from weights ``w``.

    Returns the loss of each step, the clipped first gradient (per
    leaf norm, float64), and the norm of each leaf's change after the
    last step.  ``rows`` limits the loss to those rows of each batch
    (the mean is then over them alone)."""
    grad_fn = _jitted("grad", _key(c), precision)
    p = jax.tree.map(lambda a: jnp.asarray(a, F32), w)
    mu = jax.tree.map(jnp.zeros_like, p)
    nu = jax.tree.map(jnp.zeros_like, p)
    losses: List[float] = []
    first_grad = None
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    with jax.default_matmul_precision("highest"):
        for i, batch in enumerate(batches):
            toks, labs = batch["tokens"], batch["labels"]
            use = range(toks.shape[0]) if rows is None else rows
            total, grads = 0.0, None
            for r in use:
                val, g = grad_fn(p, jnp.asarray(toks[r]),
                                 jnp.asarray(labs[r]))
                total += float(val)
                grads = g if grads is None else jax.tree.map(
                    jnp.add, grads, g)
            n_tok = len(use) * toks.shape[1]
            grads = jax.tree.map(lambda g: g / n_tok, grads)
            losses.append(total / n_tok)
            gn = float(jnp.sqrt(sum(jnp.sum(g * g)
                                    for g in jax.tree.leaves(grads))))
            clip = min(1.0, opt["grad_clip"] / max(gn, 1e-9))
            grads = jax.tree.map(lambda g: g * clip, grads)
            if first_grad is None:
                first_grad = leaf_norms(grads)
            t = i + 1
            lr = _lr(opt["start_step"] + i, opt)
            mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
            nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                              nu, grads)

            def upd(q, m, v):
                d = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
                if q.ndim >= 2:
                    d = d + opt["weight_decay"] * q
                return q - lr * d
            p = jax.tree.map(upd, p, mu, nu)
    change = leaf_norms(jax.tree.map(lambda q, a: q - a.astype(F32), p, w))
    return {"losses": losses, "first_grad": first_grad, "change": change}


def leaf_norms(tree) -> dict:
    """{path: norm} of every leaf, summed in float32 on the device."""
    return {jax.tree_util.keystr(path): float(_norm(leaf))
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


@jax.jit
def _norm(x):
    x = x.astype(F32)
    return jnp.sqrt(jnp.sum(x * x))
