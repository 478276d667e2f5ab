"""The plain float32 training reference of ``reference.py``, laid out
for a model whose training state no chip holds alone.

The mathematics is ``reference.train_steps``'s, and so is what it
returns: the same norms, rotary embedding, products (float32 under
``jax.default_matmul_precision("highest")``, or rounded to float8 for
the control), head, schedule, clipping and AdamW, mostly by calling
``reference.py``'s own functions.  What differs is where it keeps
things, so that it fits beside the weights the driver holds, and how
often it compiles:

* the precision is data, not a trace-time choice, so the float32 pass
  and the float8 control run the same compiled programs;
* the float32 weights, both AdamW moments and the gradient take the
  sharding of the weights they start from, and each is updated in
  place (donated) rather than copied;
* a row's gradient is computed one layer at a time: the forward keeps
  each layer's input, and the backward pulls the cotangent through
  one layer at a time (``jax.vjp`` of the layer), adding that layer's
  gradient into the stacked accumulator;
* attention runs over blocks of ``Q_BLOCK`` queries, each recomputed in
  the backward pass, so no (heads, seq, seq) score tensor is whole.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp

import reference
from reference import F32, _lr, _rms, _rope, _round_fp8, leaf_norms

__all__ = ["train_steps", "leaf_norms"]

#: queries per attention block
Q_BLOCK = 512


def _mm(x, w, fp8):
    """``reference._mm`` with the precision as data, so that the float32
    pass and the float8 control run the same compiled programs."""
    x, w = x.astype(F32), w.astype(F32)
    x = jnp.where(fp8, _round_fp8(x), x)
    w = jnp.where(fp8, _round_fp8(w), w)
    return x @ w


def _attention(c: dict, q, k, v):
    """Causal (and windowed) attention of q (s, h, d), already roped and
    scaled, over k and v (s, h, d), in blocks of queries."""
    s, h, d = q.shape
    bq = min(Q_BLOCK, s)
    ki = jnp.arange(s)[None, :]

    def block(args):
        qb, start = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k)
        qi = start + jnp.arange(bq)[:, None]
        mask = ki <= qi
        if c.get("sliding_window"):
            mask &= ki > qi - c["sliding_window"]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    starts = jnp.arange(0, s, bq)
    out = jax.lax.map(jax.checkpoint(block),
                      (q.reshape(s // bq, bq, h, d), starts))
    return out.reshape(s, h, d)


def _layer(c: dict, fp8, x, w):
    """``reference._layer`` with the attention in query blocks."""
    s = x.shape[0]
    hq, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    eps = c["rms_norm_eps"]
    h = _rms(x, w["norm1"]["scale"], eps)
    a = w["attn"]
    q = _rope(_mm(h, a["wq"], fp8).reshape(s, hq, hd),
              c["rope_theta"])
    k = _rope(_mm(h, a["wk"], fp8).reshape(s, hkv, hd),
              c["rope_theta"])
    v = _mm(h, a["wv"], fp8).reshape(s, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    o = _attention(c, q * hd ** -0.5, k, v)
    x = x + _mm(o.reshape(s, hq * hd), a["wo"], fp8)
    h = _rms(x, w["norm2"]["scale"], eps)
    m = w["mlp"]
    g = jax.nn.silu(_mm(h, m["w_gate"], fp8)) \
        * _mm(h, m["w_up"], fp8)
    return x + _mm(g, m["w_down"], fp8)


class _Fns:
    """The jitted pieces of one row's loss and gradient; ``fp8`` (a
    boolean scalar) selects the control."""

    def __init__(self, c: dict):
        def at(stacked, i):
            return jax.tree.map(lambda a: a[i], stacked)

        def layer_bwd(fp8, x, stacked, i, gy, acc):
            _, vjp = jax.vjp(functools.partial(_layer, c, fp8), x,
                             at(stacked, i))
            gx, gw = vjp(gy)
            return gx, jax.tree.map(lambda a, g: a.at[i].add(g), acc, gw)

        def head(fp8, x, scale, lm_head, labels):
            def loss(x, scale, lm_head):
                lg = _mm(_rms(x, scale, c["rms_norm_eps"]), lm_head, fp8)
                gold = jnp.take_along_axis(lg, labels[:, None], -1)[:, 0]
                return jnp.sum(jax.nn.logsumexp(lg, -1) - gold)
            val, (gx, gs, gh) = jax.value_and_grad(
                loss, argnums=(0, 1, 2))(x, scale, lm_head)
            return val, gx, gs, gh

        self.embed = jax.jit(
            lambda table, tokens: jnp.take(table, tokens, axis=0))
        self.layer = jax.jit(lambda fp8, x, stacked, i: _layer(
            c, fp8, x, at(stacked, i)))
        self.layer_bwd = jax.jit(layer_bwd, donate_argnums=(5,))
        self.head = jax.jit(head)
        self.add = jax.jit(lambda acc, g: jax.tree.map(jnp.add, acc, g),
                           donate_argnums=(0,))
        self.embed_bwd = jax.jit(
            lambda acc, tokens, gx: acc.at[tokens].add(gx),
            donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _fns(c_items: tuple) -> _Fns:
    return _Fns(dict(c_items))


def _row(f: _Fns, fp8, p: dict, acc: dict, tokens, labels):
    """Add one row's summed-loss gradient into ``acc``; its loss."""
    stacked = p["layers"]["u0"]
    x = f.embed(p["embed"], tokens).astype(F32)
    inputs = []
    for i in range(stacked["norm1"]["scale"].shape[0]):
        inputs.append(x)
        x = f.layer(fp8, x, stacked, jnp.int32(i))
    val, gx, gs, gh = f.head(fp8, x, p["final_norm"]["scale"],
                             p["lm_head"], labels)
    acc["final_norm"], acc["lm_head"] = f.add(
        (acc["final_norm"], acc["lm_head"]), ({"scale": gs}, gh))
    layers = acc["layers"]["u0"]
    for i in reversed(range(len(inputs))):
        gx, layers = f.layer_bwd(fp8, inputs[i], stacked, jnp.int32(i),
                                 gx, layers)
    acc["layers"] = {"u0": layers}
    acc["embed"] = f.embed_bwd(acc["embed"], tokens, gx)
    return float(val)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adamw(p, mu, nu, acc, h):
    """One AdamW step from the summed gradient ``acc``, as
    ``reference.train_steps`` takes it; ``h`` holds its scalars, each
    computed on the host as the reference computes it."""
    g = jax.tree.map(lambda a: a / h["n_tok"] * h["clip"], acc)
    mu = jax.tree.map(lambda m, g: h["b1"] * m + h["1-b1"] * g, mu, g)
    nu = jax.tree.map(lambda v, g: h["b2"] * v + h["1-b2"] * g * g, nu, g)

    def upd(q, m, v):
        d = (m / h["1-b1^t"]) / (jnp.sqrt(v / h["1-b2^t"]) + h["eps"])
        if q.ndim >= 2:
            d = d + h["wd"] * q
        return q - h["lr"] * d
    return jax.tree.map(upd, p, mu, nu), mu, nu


def _sharded_like(w, fn):
    return jax.jit(fn, out_shardings=jax.tree.map(lambda a: a.sharding,
                                                  w))(w)


def train_steps(c: dict, w: dict, batches: Sequence[dict], opt: dict, *,
                precision: str = "f32", rows: Sequence[int] = None
                ) -> dict:
    """``reference.train_steps``, laid out as the module docstring says."""
    f = _fns(reference._key(c))
    fp8 = jnp.asarray(precision == "fp8")
    p = _sharded_like(w, lambda w: jax.tree.map(
        lambda a: a.astype(F32), w))
    mu = _sharded_like(p, lambda p: jax.tree.map(jnp.zeros_like, p))
    nu = _sharded_like(p, lambda p: jax.tree.map(jnp.zeros_like, p))
    losses: List[float] = []
    first_grad = None
    with jax.default_matmul_precision("highest"):
        for i, batch in enumerate(batches):
            toks, labs = batch["tokens"], batch["labels"]
            use = range(toks.shape[0]) if rows is None else rows
            acc = _sharded_like(p, lambda p: jax.tree.map(jnp.zeros_like,
                                                          p))
            total = 0.0
            for r in use:
                total += _row(f, fp8, p, acc, jnp.asarray(toks[r]),
                              jnp.asarray(labs[r]))
            n_tok = len(use) * toks.shape[1]
            losses.append(total / n_tok)
            gn = float(jnp.sqrt(sum(jnp.sum((g / n_tok) ** 2)
                                    for g in jax.tree.leaves(acc))))
            clip = min(1.0, opt["grad_clip"] / max(gn, 1e-9))
            if first_grad is None:
                first_grad = leaf_norms(jax.tree.map(
                    lambda a: a / n_tok * clip, acc))
            b1, b2, t = opt["b1"], opt["b2"], i + 1
            p, mu, nu = _adamw(p, mu, nu, acc, {
                "n_tok": float(n_tok), "clip": clip, "b1": b1,
                "1-b1": 1 - b1, "b2": b2, "1-b2": 1 - b2,
                "1-b1^t": 1 - b1 ** t, "1-b2^t": 1 - b2 ** t,
                "eps": opt["eps"], "wd": opt["weight_decay"],
                "lr": _lr(opt["start_step"] + i, opt)})
    change = leaf_norms(jax.tree.map(lambda q, a: q - a.astype(F32), p, w))
    return {"losses": losses, "first_grad": first_grad, "change": change}
