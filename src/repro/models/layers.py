"""Shared model layers: norms, rotary embeddings, MLPs, GQA attention
(with KV cache + sliding window), embeddings, chunked cross-entropy.

Params are plain pytrees (nested dicts of jnp arrays); every layer is a
pair of pure functions ``init_*(key, ...) -> params`` and
``*(params, x, ...) -> y``.  All dense projections route through the
planned :func:`repro.ops.gemm` (GemmSpec -> plan -> execute) so the
paper's tiled-GEMM layer is the compute substrate of every
architecture.

Every region of a step carries a ``jax.named_scope``, so that each op of
the compiled program (forward, remat recompute and backward alike) names
its region in its HLO ``op_name`` and a device trace can be summed by
region.  The scopes are always on, written as literals where they open:

* ``embed`` — the token embedding and absolute positions (``forward``);
* ``layers`` — the scan over the stacked layers and the tail layers;
* ``norm`` — every RMS / layer norm;
* ``qkv`` — the q/k/v projections with rope, and cross-attention k/v;
* ``attention`` — the attention kernel call (its custom-VJP backward too);
* ``attn_out`` — the output projection with its residual epilogue;
* ``mlp`` — the dense MLP (down projection included) or the MoE FFN;
* ``lm_head_xent`` — the chunked lm-head softmax cross-entropy;
* ``grad_clip`` — global-norm gradient clipping;
* ``optimizer`` — the optimizer update and its learning-rate schedule;
* ``kv_write`` — the paged K/V pool update (serving);
* ``sample`` — token sampling from the logits (serving).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import ops
from repro.dist import sharding as shd
from repro.kernels.ref import NEG_INF


def _split(key, n):
    return jax.random.split(key, n)


def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    std = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * std) \
        .astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rms_norm(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rms_norm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    with jax.named_scope("norm"):
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * params["scale"]
        return out.astype(x.dtype)


def init_layer_norm(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def layer_norm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    with jax.named_scope("norm"):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mean) * jax.lax.rsqrt(var + eps) * params["scale"] \
            + params["bias"]
        return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (b, s, h, d) with even d; positions: (b, s) or (s,)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (b, s, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_swiglu(key, d: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = _split(key, 3)
    return {"w_gate": dense_init(k1, d, d_ff, dtype),
            "w_up": dense_init(k2, d, d_ff, dtype),
            "w_down": dense_init(k3, d_ff, d, dtype)}


def swiglu(params: dict, x: jax.Array,
           residual: Optional[jax.Array] = None) -> jax.Array:
    """SwiGLU through the fused dual-B gated kernel: one call computes
    silu(x W_gate) * (x W_up) with a single resident x stream — the
    (m, d_ff) gate/up intermediates never round-trip through HBM the way
    the old three-GEMM + XLA-silu composition did.  ``residual`` (the
    transformer residual-stream x) fuses into the down-projection's
    flush."""
    h = ops.gemm(x, params["w_gate"], b2=params["w_up"],
                 activation="silu")
    h = shd.act(h, ("batch", None, "model"))
    return ops.gemm(h, params["w_down"], residual=residual)


def init_gelu_mlp(key, d: int, d_ff: int, dtype) -> dict:
    k1, k2 = _split(key, 2)
    return {"w_in": dense_init(k1, d, d_ff, dtype),
            "w_out": dense_init(k2, d_ff, d, dtype)}


def gelu_mlp(params: dict, x: jax.Array,
             residual: Optional[jax.Array] = None) -> jax.Array:
    h = ops.gemm(x, params["w_in"], activation="gelu")
    h = shd.act(h, ("batch", None, "model"))
    return ops.gemm(h, params["w_out"], residual=residual)


# ---------------------------------------------------------------------------
# GQA attention with KV cache + sliding window
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnLayerSpec:
    """Layer *configuration* (weights + head geometry) — distinct from
    ``ops.AttnSpec``, which describes one attention *operation* to the
    kernel planner."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: int = 0          # 0 = full attention
    rope_theta: float = 10000.0
    causal: bool = True
    use_rope: bool = True


def init_attention(key, spec: AttnLayerSpec, dtype) -> dict:
    k1, k2, k3, k4 = _split(key, 4)
    d, hd = spec.d_model, spec.head_dim
    return {
        "wq": dense_init(k1, d, spec.n_heads * hd, dtype),
        "wk": dense_init(k2, d, spec.n_kv_heads * hd, dtype),
        "wv": dense_init(k3, d, spec.n_kv_heads * hd, dtype),
        "wo": dense_init(k4, spec.n_heads * hd, d, dtype),
    }


def _project_qkv(params, x, spec: AttnLayerSpec, positions):
    b, s, _ = x.shape
    with jax.named_scope("qkv"):
        q = ops.gemm(x, params["wq"]).reshape(b, s, spec.n_heads,
                                              spec.head_dim)
        k = ops.gemm(x, params["wk"]).reshape(b, s, spec.n_kv_heads,
                                              spec.head_dim)
        v = ops.gemm(x, params["wv"]).reshape(b, s, spec.n_kv_heads,
                                              spec.head_dim)
        if spec.use_rope:
            q = rope(q, positions, spec.rope_theta)
            k = rope(k, positions, spec.rope_theta)
        return q, k, v


def project_kv(params: dict, memory: jax.Array, spec: AttnLayerSpec
               ) -> Tuple[jax.Array, jax.Array]:
    """Project cross-attention k/v heads from raw encoder memory."""
    b, f, _ = memory.shape
    with jax.named_scope("qkv"):
        k = ops.gemm(memory, params["wk"]).reshape(b, f, spec.n_kv_heads,
                                                   spec.head_dim)
        v = ops.gemm(memory, params["wv"]).reshape(b, f, spec.n_kv_heads,
                                                   spec.head_dim)
        return k, v


def attention_block(params: dict, x: jax.Array, spec: AttnLayerSpec,
                    positions: Optional[jax.Array] = None,
                    kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                    memory: Optional[jax.Array] = None,
                    residual: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence (train / prefill / encoder) attention.

    Cross-attention: pass ``memory`` (raw (b, f, d) encoder output — k/v
    are projected here) or ``kv`` (already-projected heads, e.g. from a
    decode cache).  Either disables causality.

    ``residual`` (the pre-norm residual-stream x) fuses into the output
    projection's kernel flush instead of a separate XLA add.
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    if kv is None and memory is None:
        q, k, v = _project_qkv(params, x, spec, positions)
        with jax.named_scope("attention"):
            out = ops.attention(q, k, v, causal=spec.causal,
                                window=spec.window)
    else:
        with jax.named_scope("qkv"):
            q = ops.gemm(x, params["wq"]).reshape(b, s, spec.n_heads,
                                                  spec.head_dim)
            if spec.use_rope:
                q = rope(q, positions, spec.rope_theta)
        if kv is None:
            kv = project_kv(params, memory, spec)
        k, v = kv
        with jax.named_scope("attention"):
            out = ops.attention(q, k, v, causal=False, window=0)
    out = shd.act(out, ("batch", None, "model", None))
    with jax.named_scope("attn_out"):
        return ops.gemm(out.reshape(b, s, -1), params["wo"],
                        residual=residual)


def init_kv_cache(batch: int, max_len: int, spec: AttnLayerSpec, dtype) -> dict:
    shape = (batch, max_len, spec.n_kv_heads, spec.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def scatter_rows(cache: jax.Array, new: jax.Array, idx: jax.Array
                 ) -> jax.Array:
    """Per-row dynamic insertion: row ``i`` of ``cache`` (b, S, ...) takes
    ``new[i]`` (1, ...) at sequence position ``idx[i]`` — the per-slot
    write primitive of continuous batching, where every batch row decodes
    at its own position."""
    return jax.vmap(
        lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
            c, n, i, axis=0))(cache, new, idx)


def attention_decode(params: dict, x: jax.Array, cache: dict,
                     pos: jax.Array, spec: AttnLayerSpec,
                     residual: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, dict]:
    """Single-step decode: insert each row's k/v at its own position
    ``pos`` ((b,) int32, scalar broadcasts) and attend over the cache
    with per-row position masking (+ sliding window).

    x: (b, 1, d).  Returns (out (b, 1, d), new cache); ``residual`` fuses
    the residual-stream add into the output projection.
    """
    b, s, _ = x.shape
    assert s == 1
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos[:, None]
    q, k_new, v_new = _project_qkv(params, x, spec, positions)

    k_cache = scatter_rows(cache["k"], k_new, pos)
    v_cache = scatter_rows(cache["v"], v_new, pos)
    # pin the cache values inside the layer loop: without this, CPU
    # XLA's bf16-dot legalization hoists a convert of the ENTIRE stacked
    # cache out of the scan and maintains a second full-precision copy
    # (full-stack rewrite per layer); on TPU the bf16 dot is native and
    # the barrier is free
    k_att, v_att = jax.lax.optimization_barrier((k_cache, v_cache))

    out = ops.decode_attention(q[:, 0], k_att, v_att, pos,
                               window=spec.window)
    out = ops.gemm(out.reshape(b, 1, -1), params["wo"],
                   residual=residual)
    return out, {"k": k_cache, "v": v_cache}


def paged_attention_decode(params: dict, x: jax.Array, cache: dict,
                           page_table: jax.Array, pos: jax.Array,
                           spec: AttnLayerSpec,
                           residual: Optional[jax.Array] = None
                           ) -> Tuple[jax.Array, dict]:
    """Single-step decode against a block-paged KV pool.

    ``cache``: {"k", "v"} of (n_pages, page_size, hkv, hd) — one pool
    shared by every slot; ``page_table``: (b, max_pages) int32 per-slot
    tables.  Row i's new k/v lands in physical page
    ``page_table[i, pos[i] // page_size]`` at offset ``pos[i] %
    page_size``; rows the engine has masked (all-sink tables) write
    into the reserved sink page, which no live table references.
    """
    b, s, _ = x.shape
    assert s == 1
    ps = cache["k"].shape[1]
    max_pages = page_table.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos[:, None]
    q, k_new, v_new = _project_qkv(params, x, spec, positions)

    rows = jnp.arange(b)
    # clamp so a masked row whose junk position overruns the table still
    # indexes in-bounds (it lands on the sink page regardless)
    pages = page_table[rows, jnp.minimum(pos // ps, max_pages - 1)]
    offs = pos % ps
    with jax.named_scope("kv_write"):
        k_cache = cache["k"].at[pages, offs].set(
            k_new[:, 0].astype(cache["k"].dtype))
        v_cache = cache["v"].at[pages, offs].set(
            v_new[:, 0].astype(cache["v"].dtype))
        # same CPU-XLA bf16-hoisting workaround as the dense path
        k_att, v_att = jax.lax.optimization_barrier((k_cache, v_cache))

    with jax.named_scope("attention"):
        out = ops.decode_attention_paged(q[:, 0], k_att, v_att, page_table,
                                         pos, window=spec.window)
    with jax.named_scope("attn_out"):
        out = ops.gemm(out.reshape(b, 1, -1), params["wo"],
                       residual=residual)
    return out, {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# Embedding + chunked cross-entropy
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02) \
        .astype(dtype)


def embed(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def chunked_softmax_xent(h: jax.Array, lm_head: jax.Array,
                         labels: jax.Array, *, n_chunks: int = 8,
                         label_mask: Optional[jax.Array] = None
                         ) -> jax.Array:
    """Cross-entropy over a large vocab without materializing full logits.

    h: (b, s, d); lm_head: (d, V); labels: (b, s) int32.  Chunks run over
    the *sequence* axis (lax.map), so each chunk keeps the batch dim —
    and with it the 'data'-axis sharding — while peak logits memory is
    (b, s/n_chunks, V) instead of (b, s, V).
    """
    b, s, d = h.shape
    n_chunks = max(1, min(n_chunks, s))
    pad = (-s) % n_chunks
    mf = jnp.ones((b, s), jnp.float32) if label_mask is None \
        else label_mask.astype(jnp.float32)
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mf = jnp.pad(mf, ((0, 0), (0, pad)))
    cs = (s + pad) // n_chunks
    hs = h.reshape(b, n_chunks, cs, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, n_chunks, cs).transpose(1, 0, 2)
    ms = mf.reshape(b, n_chunks, cs).transpose(1, 0, 2)

    def chunk_loss(args):
        hc, lc, mc = args                       # (b, cs, d) / (b, cs)
        # fp32 logits come straight out of the GEMM accumulator
        # (out_dtype) — no bf16 logits tensor is written and re-upcast,
        # and the reference path keeps operands at storage dtype
        # (preferred_element_type accumulation), so no fp32 copy of
        # lm_head round-trips HBM either
        logits = ops.gemm(hc, lm_head, out_dtype=jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - gold) * mc), jnp.sum(mc)

    losses, counts = jax.lax.map(jax.checkpoint(chunk_loss), (hs, ls, ms))
    return jnp.sum(losses) / jnp.maximum(jnp.sum(counts), 1.0)
