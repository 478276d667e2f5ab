"""Composable decoder stack covering all ten assigned architectures.

A model is a repeated ``layer_pattern`` unit (e.g. ('rec','rec','local')
for recurrentgemma) scanned over ``cfg.repeats`` repetitions with
optional remat — so a 95-layer model lowers to one while-loop and the
HLO stays compact for the 40-cell multi-pod dry-run.

Three execution paths per architecture:
  * :func:`forward` / :func:`loss_fn`    — training (full seq, remat+scan)
  * :func:`prefill`                      — fill caches from a prompt
  * :func:`decode_step`                  — one token with caches (serve)

Family add-ons: encoder-decoder w/ cross-attention (whisper), prefix
patch embeddings (internvl2).  Modality frontends are stubs per the task
sheet: ``input_specs`` feeds precomputed frame/patch embeddings.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist import sharding as shd
from repro import ops
from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models import moe as MOE
from repro.models import rglru as RG

AUX_LOSS_WEIGHT = 0.01


# ---------------------------------------------------------------------------
# Specs / init
# ---------------------------------------------------------------------------

def _sinusoid(positions: jax.Array, d: int) -> jax.Array:
    """Absolute sinusoidal position encoding (audio family — whisper uses
    absolute positions, not RoPE)."""
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(10000.0) / max(half - 1, 1)))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _maybe_abs_pos(cfg: ModelConfig, x: jax.Array, start: jax.Array | int
                   ) -> jax.Array:
    """``start`` may be a scalar (whole batch at one offset — train /
    prefill) or a (b,) per-slot vector (continuous-batching decode, each
    row at its own position)."""
    if cfg.use_rope:
        return x
    s, d = x.shape[1], x.shape[2]
    start = jnp.asarray(start)
    if start.ndim == 1:
        pos = jnp.arange(s)[None, :] + start[:, None]       # (b, s)
        return x + _sinusoid(pos, d).astype(x.dtype)
    pos = jnp.arange(s) + start
    return x + _sinusoid(pos, d)[None].astype(x.dtype)


def _attn_spec(cfg: ModelConfig, kind: str, *, causal: bool = True
               ) -> L.AttnLayerSpec:
    window = cfg.window if kind in ("attn", "moe") else cfg.local_window
    return L.AttnLayerSpec(
        d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, window=window,
        rope_theta=cfg.rope_theta, causal=causal, use_rope=cfg.use_rope)


def _norm_init(cfg: ModelConfig):
    return L.init_layer_norm(cfg.d_model) if cfg.family == "audio" \
        else L.init_rms_norm(cfg.d_model)


def _norm(cfg: ModelConfig, p, x):
    return L.layer_norm(p, x) if cfg.family == "audio" \
        else L.rms_norm(p, x, cfg.norm_eps)


def _mlp_init(key, cfg: ModelConfig):
    if cfg.family == "audio":
        return L.init_gelu_mlp(key, cfg.d_model, cfg.d_ff, cfg.dtype)
    return L.init_swiglu(key, cfg.d_model, cfg.d_ff, cfg.dtype)


def _mlp(cfg: ModelConfig, p, x, residual=None):
    """MLP through the fused kernels; ``residual`` rides the down
    projection's epilogue (one C write instead of GEMM + XLA add)."""
    with jax.named_scope("mlp"):
        if cfg.family == "audio":
            return L.gelu_mlp(p, x, residual=residual)
        return L.swiglu(p, x, residual=residual)


def init_layer(key, cfg: ModelConfig, kind: str) -> dict:
    ks = jax.random.split(key, 4)
    dt = cfg.dtype
    if kind in ("attn", "local"):
        return {"norm1": _norm_init(cfg),
                "attn": L.init_attention(ks[0], _attn_spec(cfg, kind), dt),
                "norm2": _norm_init(cfg),
                "mlp": _mlp_init(ks[1], cfg)}
    if kind == "moe":
        return {"norm1": _norm_init(cfg),
                "attn": L.init_attention(ks[0], _attn_spec(cfg, kind), dt),
                "norm2": _norm_init(cfg),
                "moe": MOE.init_moe(ks[1], cfg.d_model, cfg.d_ff,
                                    cfg.n_experts, dt)}
    if kind == "ssm":
        return {"norm1": _norm_init(cfg),
                "mixer": M2.init_mamba2(ks[0], cfg.d_model, cfg.ssm_state,
                                        dt)}
    if kind == "rec":
        return {"norm1": _norm_init(cfg),
                "rec": RG.init_rglru(ks[0], cfg.d_model,
                                     cfg.lru_width or cfg.d_model, dt),
                "norm2": _norm_init(cfg),
                "mlp": _mlp_init(ks[1], cfg)}
    raise ValueError(kind)


def _init_decoder_layer(key, cfg: ModelConfig, kind: str) -> dict:
    p = init_layer(key, cfg, kind)
    if cfg.encoder_layers:                 # audio: add cross-attention
        kc = jax.random.fold_in(key, 777)
        p["norm_x"] = _norm_init(cfg)
        p["cross"] = L.init_attention(
            kc, _attn_spec(cfg, kind, causal=False), cfg.dtype)
    return p


def init_params(key, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, 8)
    params: Dict = {
        "embed": L.init_embedding(keys[0], cfg.vocab, cfg.d_model,
                                  cfg.dtype),
        "final_norm": _norm_init(cfg),
        "lm_head": L.dense_init(keys[1], cfg.d_model, cfg.vocab, cfg.dtype),
        "layers": {},
    }
    for i, kind in enumerate(cfg.layer_pattern):
        lk = jax.random.fold_in(keys[2], i)
        params["layers"][f"u{i}"] = jax.vmap(
            lambda k: _init_decoder_layer(k, cfg, kind))(
                jax.random.split(lk, cfg.repeats))
    if cfg.tail_pattern:
        assert not cfg.encoder_layers, "tail + enc-dec unsupported"
        params["tail"] = {
            f"t{i}": init_layer(jax.random.fold_in(keys[4], i), cfg, kind)
            for i, kind in enumerate(cfg.tail_pattern)}
    if cfg.encoder_layers:
        enc: Dict = {"final_norm": _norm_init(cfg), "layers": {}}
        ek = jax.random.fold_in(keys[3], 0)
        enc["layers"]["u0"] = jax.vmap(
            lambda k: init_layer(k, cfg, "attn"))(
                jax.random.split(ek, cfg.encoder_layers))
        params["encoder"] = enc
    return params


# ---------------------------------------------------------------------------
# Full-sequence apply (training / encoder)
# ---------------------------------------------------------------------------

def apply_layer(p: dict, cfg: ModelConfig, kind: str, x: jax.Array, *,
                enc_out: Optional[jax.Array] = None,
                causal: bool = True) -> Tuple[jax.Array, jax.Array]:
    """One layer, full sequence.  Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    spec = _attn_spec(cfg, kind, causal=causal)
    if kind in ("attn", "local", "moe"):
        # the residual-stream adds fuse into the output/down projections'
        # kernel flushes (epilogue) — no separate XLA add round-trips
        x = L.attention_block(p["attn"], _norm(cfg, p["norm1"], x),
                              spec, residual=x)
        if enc_out is not None:
            x = L.attention_block(p["cross"],
                                  _norm(cfg, p["norm_x"], x), spec,
                                  memory=enc_out, residual=x)
        h = _norm(cfg, p["norm2"], x)
        if kind == "moe":
            y, aux = MOE.moe_ffn(p["moe"], h, top_k=cfg.top_k,
                                 capacity_factor=cfg.capacity_factor)
            x = x + y
        else:
            x = _mlp(cfg, p["mlp"], h, residual=x)
    elif kind == "ssm":
        x = x + M2.mamba2_block(p["mixer"], _norm(cfg, p["norm1"], x),
                                cfg.ssm_state)
    elif kind == "rec":
        x = x + RG.rglru_block(p["rec"], _norm(cfg, p["norm1"], x))
        x = _mlp(cfg, p["mlp"], _norm(cfg, p["norm2"], x), residual=x)
    else:
        raise ValueError(kind)
    return x, aux


def _encode(params: dict, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """Encoder stack over stub frame embeddings (b, F, d)."""
    enc = params["encoder"]

    def unit(x, p):
        y, _ = apply_layer(p, cfg, "attn", x, causal=False)
        return y, None

    x, _ = jax.lax.scan(unit, frames, enc["layers"]["u0"])
    return _norm(cfg, enc["final_norm"], x)


def _project_cross_kv(p: dict, cfg: ModelConfig, enc_out: jax.Array):
    return L.project_kv(p["cross"], enc_out, _attn_spec(cfg, "attn"))


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array, *,
            prefix_embeds: Optional[jax.Array] = None,
            frames: Optional[jax.Array] = None,
            remat: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  Returns (hidden (b, s, d), aux_loss).

    ``prefix_embeds`` (vlm): (b, P, d) prepended to token embeddings.
    ``frames`` (audio): (b, F, d) stub encoder input.
    """
    with jax.named_scope("embed"):
        x = L.embed(params["embed"], tokens)
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        x = _maybe_abs_pos(cfg, x, 0)
    # 'seq' resolves to 'model' under sequence parallelism (the stored
    # remat carry is then 1/|model| per device), else to None
    x = shd.act(x, ("batch", "seq", None))
    enc_out = _encode(params, cfg, frames) if frames is not None else None

    kinds = cfg.layer_pattern

    def unit(carry, p_unit):
        h, aux = carry
        for i, kind in enumerate(kinds):
            h, a = apply_layer(p_unit[f"u{i}"], cfg, kind, h,
                               enc_out=enc_out)
            aux = aux + a
        h = shd.act(h, ("batch", "seq", None))
        return (h, aux), None

    fn = jax.checkpoint(unit) if remat else unit
    with jax.named_scope("layers"):
        (x, aux), _ = jax.lax.scan(fn, (x, jnp.zeros((), jnp.float32)),
                                   params["layers"])
        for i, kind in enumerate(cfg.tail_pattern):
            x, a = apply_layer(params["tail"][f"t{i}"], cfg, kind, x,
                               enc_out=enc_out)
            aux = aux + a
    return _norm(cfg, params["final_norm"], x), aux


def loss_fn(params: dict, cfg: ModelConfig, batch: dict, *,
            n_chunks: int = 8, remat: bool = True
            ) -> Tuple[jax.Array, dict]:
    """batch: tokens (b,s), labels (b,s), optional mask/frames/prefix."""
    h, aux = forward(params, cfg, batch["tokens"],
                     prefix_embeds=batch.get("prefix_embeds"),
                     frames=batch.get("frames"), remat=remat)
    labels = batch["labels"]
    mask = batch.get("mask")
    if batch.get("prefix_embeds") is not None:
        p = batch["prefix_embeds"].shape[1]
        h = h[:, p:]                       # loss over text positions only
    with jax.named_scope("lm_head_xent"):
        ce = L.chunked_softmax_xent(h, params["lm_head"], labels,
                                    n_chunks=n_chunks, label_mask=mask)
    loss = ce + AUX_LOSS_WEIGHT * aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# KV / state caches
# ---------------------------------------------------------------------------

def _cache_len(cfg: ModelConfig, kind: str, max_len: int) -> int:
    """Sliding-window layers only ever need `window` cache slots — this is
    what makes long_500k feasible for SWA/hybrid archs."""
    window = cfg.window if kind in ("attn", "moe") else cfg.local_window
    return min(max_len, window) if window > 0 else max_len


def init_layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int
                     ) -> dict:
    dt = jnp.dtype(cfg.dtype)
    if kind in ("attn", "local", "moe"):
        spec = _attn_spec(cfg, kind)
        c = L.init_kv_cache(batch, _cache_len(cfg, kind, max_len), spec, dt)
    elif kind == "ssm":
        c = M2.init_mamba2_cache(batch, cfg.d_model, cfg.ssm_state, dt)
    elif kind == "rec":
        c = RG.init_rglru_cache(batch, cfg.lru_width or cfg.d_model, dt)
    else:
        raise ValueError(kind)
    return c


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """``pos`` is a (batch,) per-slot position vector: every batch slot
    decodes at its own position (the continuous-batching cache
    contract), so a freshly admitted request can sit next to one that is
    hundreds of tokens into its generation."""
    cache: Dict = {"pos": jnp.zeros((batch,), jnp.int32), "layers": {}}

    def stack(make):
        return jax.vmap(lambda _: make())(jnp.arange(cfg.repeats))

    for i, kind in enumerate(cfg.layer_pattern):
        cache["layers"][f"u{i}"] = stack(
            lambda kind=kind: init_layer_cache(cfg, kind, batch, max_len))
    if cfg.tail_pattern:
        cache["tail"] = {
            f"t{i}": init_layer_cache(cfg, kind, batch, max_len)
            for i, kind in enumerate(cfg.tail_pattern)}
    if cfg.encoder_layers:
        spec = _attn_spec(cfg, "attn")
        f = cfg.encoder_seq
        shape = (batch, f, spec.n_kv_heads, spec.head_dim)
        cache["cross"] = {
            f"u{i}": {"k": jnp.zeros((cfg.repeats,) + shape, cfg.dtype),
                      "v": jnp.zeros((cfg.repeats,) + shape, cfg.dtype)}
            for i in range(len(cfg.layer_pattern))}
    return cache


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def _sliding_pos(cfg: ModelConfig, kind: str, pos: jax.Array,
                 cache_max: int) -> jax.Array:
    """Ring-buffer write position for bounded (windowed) caches.
    Elementwise, so a (b,) per-slot position vector maps to (b,) ring
    write positions."""
    return jnp.remainder(pos, cache_max)


def decode_layer(p: dict, cache: dict, cfg: ModelConfig, kind: str,
                 x: jax.Array, pos: jax.Array,
                 cross_kv=None, page_table=None) -> Tuple[jax.Array, dict]:
    spec = _attn_spec(cfg, kind)
    if kind in ("attn", "local", "moe"):
        h = _norm(cfg, p["norm1"], x)
        if page_table is not None:
            # block-paged pool: windowed layers page at full length and
            # window-mask in the kernel (the ring-buffer optimization is
            # a dense-cache feature)
            x, cache = L.paged_attention_decode(
                p["attn"], h, cache, page_table, pos, spec, residual=x)
        elif spec.window > 0 and cache["k"].shape[1] <= spec.window:
            # bounded ring-buffer cache (the long_500k enabler)
            wpos = _sliding_pos(cfg, kind, pos, cache["k"].shape[1])
            x, cache = _decode_ring(p, cache, spec, h, pos, wpos,
                                    residual=x)
        else:
            x, cache = L.attention_decode(p["attn"], h, cache, pos, spec,
                                          residual=x)
        if cross_kv is not None:
            q = _norm(cfg, p["norm_x"], x)
            x = L.attention_block(
                p["cross"], q, spec, kv=(cross_kv["k"], cross_kv["v"]),
                residual=x)
        h = _norm(cfg, p["norm2"], x)
        if kind == "moe":
            y, _ = MOE.moe_ffn(p["moe"], h, top_k=cfg.top_k,
                               capacity_factor=4.0)
            x = x + y
        else:
            x = _mlp(cfg, p["mlp"], h, residual=x)
    elif kind == "ssm":
        y, cache = M2.mamba2_decode(p["mixer"], _norm(cfg, p["norm1"], x),
                                    cache, cfg.ssm_state)
        x = x + y
    elif kind == "rec":
        y, cache = RG.rglru_decode(p["rec"], _norm(cfg, p["norm1"], x),
                                   cache)
        x = x + y
        x = _mlp(cfg, p["mlp"], _norm(cfg, p["norm2"], x), residual=x)
    return x, cache


def _decode_ring(p, cache, spec: L.AttnLayerSpec, x, pos, wpos,
                 residual=None):
    """Windowed decode against a ring-buffer cache of size <= window:
    every resident entry is in-window by construction, so attention masks
    only un-written slots.  ``pos``/``wpos`` are (b,) per-slot vectors —
    each row writes at its own ring offset and masks at its own
    fill level."""
    b = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    wpos = jnp.broadcast_to(jnp.asarray(wpos, jnp.int32), (b,))
    positions = pos[:, None]
    q, k_new, v_new = L._project_qkv(p["attn"], x, spec, positions)
    k_cache = L.scatter_rows(cache["k"], k_new, wpos)
    v_cache = L.scatter_rows(cache["v"], v_new, wpos)
    groups = spec.n_heads // spec.n_kv_heads
    cache_max = k_cache.shape[1]
    # bf16 operands + fp32 accumulation: never materialize an f32 cache
    qg = q.reshape(b, 1, spec.n_kv_heads, groups, spec.head_dim)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache,
                        preferred_element_type=jnp.float32) \
        * spec.head_dim ** -0.5
    slot = jnp.arange(cache_max)
    written = slot[None, :] <= pos[:, None]   # before first wrap
    written |= pos[:, None] >= cache_max      # after wrap: all slots valid
    logits = jnp.where(written[:, None, None, None, :], logits,
                       jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32) \
        .astype(x.dtype)
    out = ops.gemm(out.reshape(b, 1, -1), p["attn"]["wo"],
                   residual=residual)
    return out, {"k": k_cache, "v": v_cache}


def decode_step(params: dict, cfg: ModelConfig, token: jax.Array,
                cache: dict) -> Tuple[jax.Array, dict]:
    """One decode step.  token: (b, 1) int32.  Returns (logits (b, V),
    updated cache).

    ``cache["pos"]`` is (b,): every batch slot decodes at its own
    position, so one compiled step serves a continuous batch of requests
    at arbitrary phases of their generations."""
    pos = jnp.broadcast_to(jnp.asarray(cache["pos"], jnp.int32),
                           (token.shape[0],))
    x = L.embed(params["embed"], token)
    x = _maybe_abs_pos(cfg, x, pos)
    kinds = cfg.layer_pattern
    table = cache.get("page_table")

    def unit(h, xs):
        p_unit, c_unit, x_unit = xs
        new_c = {}
        for i, kind in enumerate(kinds):
            ck = f"u{i}"
            h, new_c[ck] = decode_layer(
                p_unit[ck], c_unit[ck], cfg, kind, h, pos,
                cross_kv=x_unit[ck] if x_unit is not None else None,
                page_table=table)
        return h, new_c

    cross = cache.get("cross")
    xs = (params["layers"], cache["layers"], cross)
    x, new_layer_cache = jax.lax.scan(unit, x, xs)
    new_cache = dict(cache, layers=new_layer_cache, pos=pos + 1)
    if cfg.tail_pattern:
        new_tail = {}
        for i, kind in enumerate(cfg.tail_pattern):
            tk = f"t{i}"
            x, new_tail[tk] = decode_layer(
                params["tail"][tk], cache["tail"][tk], cfg, kind, x, pos,
                page_table=table)
        new_cache["tail"] = new_tail
    x = _norm(cfg, params["final_norm"], x)
    logits = ops.gemm(x[:, 0], params["lm_head"], out_dtype=jnp.float32)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill_layer(p: dict, cache: dict, cfg: ModelConfig, kind: str,
                  x: jax.Array, cross_kv=None) -> Tuple[jax.Array, dict]:
    """Full-prompt forward that also fills this layer's cache (fresh cache,
    prompt starts at position 0)."""
    b, s, _ = x.shape
    spec = _attn_spec(cfg, kind)
    if kind in ("attn", "local", "moe"):
        h = _norm(cfg, p["norm1"], x)
        positions = jnp.arange(s)
        q, k, v = L._project_qkv(p["attn"], h, spec, positions)
        out = ops.attention(q, k, v, causal=True, window=spec.window)
        out = ops.gemm(out.reshape(b, s, -1), p["attn"]["wo"],
                       residual=x)
        cache_max = cache["k"].shape[1]
        if cache_max >= s:
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, 1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, 1)
        else:   # windowed ring buffer: keep the tail, ring-aligned
            tail_k, tail_v = k[:, s - cache_max:], v[:, s - cache_max:]
            shift = jnp.remainder(s - cache_max, cache_max)
            ck = jnp.roll(tail_k, shift, axis=1)
            cv = jnp.roll(tail_v, shift, axis=1)
        cache = {"k": ck, "v": cv}
        x = out
        if cross_kv is not None:
            qx = _norm(cfg, p["norm_x"], x)
            x = L.attention_block(
                p["cross"], qx, spec, kv=(cross_kv["k"], cross_kv["v"]),
                residual=x)
        hh = _norm(cfg, p["norm2"], x)
        if kind == "moe":
            y, _ = MOE.moe_ffn(p["moe"], hh, top_k=cfg.top_k,
                               capacity_factor=cfg.capacity_factor)
            x = x + y
        else:
            x = _mlp(cfg, p["mlp"], hh, residual=x)
    elif kind == "ssm":
        h = _norm(cfg, p["norm1"], x)
        y, cache = _mamba2_prefill(p["mixer"], h, cache, cfg.ssm_state)
        x = x + y
    elif kind == "rec":
        h = _norm(cfg, p["norm1"], x)
        y, cache = _rglru_prefill(p["rec"], h, cache)
        x = x + y
        x = _mlp(cfg, p["mlp"], _norm(cfg, p["norm2"], x), residual=x)
    return x, cache


def _mamba2_prefill(p, x, cache, d_state):
    bsz, s, d_model = x.shape
    dd = M2.dims(d_model, d_state)
    proj = ops.gemm(x, p["in_proj"])
    z, xs, b_, c_, dt = M2._split_proj(proj, d_model, d_state)
    conv_in = jnp.concatenate([xs, b_, c_], axis=-1)
    conv_out, conv_state = M2._causal_conv(conv_in, p["conv_w"],
                                           p["conv_b"], cache["conv"])
    xs = conv_out[..., :dd["d_inner"]]
    b_ = conv_out[..., dd["d_inner"]:dd["d_inner"] + d_state]
    c_ = conv_out[..., dd["d_inner"] + d_state:]
    xh = xs.reshape(bsz, s, dd["heads"], dd["head_dim"])
    y, state = M2.ssd_chunked(xh, dt, p["a_log"], b_, c_, p["d_skip"],
                              p["dt_bias"], init_state=cache["ssd"])
    y = y.reshape(bsz, s, dd["d_inner"])
    y = L.rms_norm(p["norm"], y) \
        * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    return ops.gemm(y, p["out_proj"]), {"conv": conv_state, "ssd": state}


def _rglru_prefill(p, x, cache):
    proj = ops.gemm(x, p["in_proj"])
    branch, gate = jnp.split(proj, 2, axis=-1)
    branch, conv_state = RG._conv(branch, p["conv_w"], p["conv_b"],
                                  cache["conv"])
    a, bx = RG._gates(p, branch)
    h = RG._lru_scan(a, bx, cache["h"])
    y = h.astype(x.dtype) \
        * jax.nn.gelu(gate.astype(jnp.float32)).astype(x.dtype)
    return ops.gemm(y, p["out_proj"]), \
        {"conv": conv_state, "h": h[:, -1, :]}


def prefill(params: dict, cfg: ModelConfig, tokens: jax.Array,
            cache: dict, *, prefix_embeds=None, frames=None
            ) -> Tuple[jax.Array, dict]:
    """Run the prompt, fill caches.  Returns (last-token logits, cache)."""
    x = L.embed(params["embed"], tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    x = _maybe_abs_pos(cfg, x, 0)
    s_total = x.shape[1]
    kinds = cfg.layer_pattern

    cross = cache.get("cross")
    if frames is not None:
        enc_out = _encode(params, cfg, frames)
        cross = {}
        for i in range(len(kinds)):
            ck = f"u{i}"
            k, v = jax.vmap(
                lambda pl: _project_cross_kv(pl, cfg, enc_out))(
                    params["layers"][ck])
            cross[ck] = {"k": k, "v": v}

    def unit(h, xs):
        p_unit, c_unit, x_unit = xs
        new_c = {}
        for i, kind in enumerate(kinds):
            ck = f"u{i}"
            h, new_c[ck] = prefill_layer(
                p_unit[ck], c_unit[ck], cfg, kind, h,
                cross_kv=x_unit[ck] if x_unit is not None else None)
        return h, new_c

    xs = (params["layers"], cache["layers"], cross)
    x, new_layer_cache = jax.lax.scan(unit, x, xs)
    new_cache = dict(cache, layers=new_layer_cache,
                     pos=jnp.full((tokens.shape[0],), s_total, jnp.int32))
    if cfg.tail_pattern:
        new_tail = {}
        for i, kind in enumerate(cfg.tail_pattern):
            tk = f"t{i}"
            x, new_tail[tk] = prefill_layer(
                params["tail"][tk], cache["tail"][tk], cfg, kind, x)
        new_cache["tail"] = new_tail
    x = _norm(cfg, params["final_norm"], x)
    logits = ops.gemm(x[:, -1], params["lm_head"], out_dtype=jnp.float32)
    if cross is not None:
        new_cache["cross"] = cross
    return logits, new_cache


# ---------------------------------------------------------------------------
# Slot-targeted prefill (continuous batching)
# ---------------------------------------------------------------------------

def _cache_batch_dim(path, leaf) -> int:
    """Batch axis of one cache leaf: leaves under the scanned ``layers``
    / ``cross`` subtrees are stacked (repeats, batch, ...); ``tail`` and
    ``pos`` leaves carry batch at dim 0 (mirrors
    :func:`repro.dist.layout.cache_specs`)."""
    keys = [str(p.key) for p in path
            if isinstance(p, jax.tree_util.DictKey)]
    stacked = bool(keys) and keys[0] in ("layers", "cross")
    return 1 if stacked and leaf.ndim >= 2 else 0


def insert_cache_slot(live: dict, sub: dict, slot: jax.Array) -> dict:
    """Scatter a batch-1 cache into batch row ``slot`` of a live
    multi-slot cache (``jax.lax.dynamic_update_slice`` on every leaf's
    batch dim) — resident slots are untouched."""
    slot = jnp.asarray(slot, jnp.int32)

    def one(path, leaf, subleaf):
        return jax.lax.dynamic_update_slice_in_dim(
            leaf, subleaf.astype(leaf.dtype), slot,
            axis=_cache_batch_dim(path, leaf))

    return jax.tree_util.tree_map_with_path(one, live, sub)


def prefill_into_slot(params: dict, cfg: ModelConfig, tokens: jax.Array,
                      cache: dict, slot: jax.Array, *, max_len: int,
                      prefix_embeds=None, frames=None
                      ) -> Tuple[jax.Array, dict]:
    """Admit ONE request into slot ``slot`` of a live multi-slot cache.

    The (1, s) prompt prefills a fresh batch-1 cache, and every leaf —
    k/v, ring/conv/SSM states, the per-slot ``pos`` — is scattered into
    the slot's batch row; resident slots keep decoding from exactly the
    state they had (no re-prefill).  Stale entries beyond the new
    request's length are invisible by construction: decode masks cache
    positions > ``pos[slot]`` and overwrites them sequentially.

    Returns (last-token logits (1, V), updated cache).  ``slot`` may be
    traced, so one compiled prefill per prompt length serves every slot.
    """
    assert tokens.shape[0] == 1, "slot prefill admits one request"
    fresh = init_cache(cfg, 1, max_len)
    logits, sub = prefill(params, cfg, tokens, fresh,
                          prefix_embeds=prefix_embeds, frames=frames)
    return logits, insert_cache_slot(cache, sub, slot)


# ---------------------------------------------------------------------------
# Block-paged KV cache (serve)
# ---------------------------------------------------------------------------

def init_paged_cache(cfg: ModelConfig, batch: int, n_pages: int,
                     page_size: int, max_pages: int) -> dict:
    """Decode cache whose attention K/V live in a shared block pool.

    Every attn-family layer gets one {"k", "v"} pool of
    ``(n_pages, page_size, n_kv_heads, head_dim)``; slots address it
    through ``cache["page_table"]`` ((batch, max_pages) int32, where
    entry 0 is the engine's reserved sink page — free or mid-prefill
    rows stay all-sink so their junk decode writes never touch live
    pages).  Windowed layers page at full length and rely on kernel
    window masking (the dense path's ring buffer doesn't apply).

    Recurrent layer kinds (ssm/rec) are rejected: their per-slot state
    has no page-table indirection, so chunked prefill would reuse the
    slot's stale state, interleaved decode bursts would mutate a
    mid-prefill slot's recurrence (only attention writes are
    sink-masked), and prefix sharing can't skip tokens through a
    recurrence.  Those archs serve through the dense engine.
    """
    assert not cfg.encoder_layers, \
        "paged cache: encoder-decoder archs unsupported"
    bad = sorted({k for k in cfg.all_kinds if k in ("ssm", "rec")})
    assert not bad, \
        f"paged cache: recurrent layer kinds {bad} unsupported"

    def paged_layer(kind):
        spec = _attn_spec(cfg, kind)
        shape = (n_pages, page_size, spec.n_kv_heads, spec.head_dim)
        dt = jnp.dtype(cfg.dtype)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    cache: Dict = {
        "pos": jnp.zeros((batch,), jnp.int32),
        "page_table": jnp.zeros((batch, max_pages), jnp.int32),
        "layers": {},
    }

    def stack(make):
        return jax.vmap(lambda _: make())(jnp.arange(cfg.repeats))

    for i, kind in enumerate(cfg.layer_pattern):
        cache["layers"][f"u{i}"] = stack(
            lambda kind=kind: paged_layer(kind))
    if cfg.tail_pattern:
        cache["tail"] = {f"t{i}": paged_layer(kind)
                         for i, kind in enumerate(cfg.tail_pattern)}
    return cache


def _prefill_chunk_layer(p: dict, cache: dict, cfg: ModelConfig,
                         kind: str, x: jax.Array,
                         table_row: jax.Array, start: int
                         ) -> Tuple[jax.Array, dict]:
    """One layer of a fixed-offset prompt chunk against the paged cache.

    ``start`` is static: the chunk's k/v scatter indices into
    ``table_row`` and the exact-length history slice are compile-time,
    so the attention call sees operands of exactly ``(s, start + s)``
    — the same per-row math (and bits) as a full-prompt reference
    prefill.  Only attn-family kinds exist here —
    :func:`init_paged_cache` rejects recurrent stacks.
    """
    b, s, _ = x.shape
    if kind not in ("attn", "local", "moe"):
        raise ValueError(f"paged chunk prefill: unsupported layer "
                         f"kind {kind!r}")
    spec = _attn_spec(cfg, kind)
    h = _norm(cfg, p["norm1"], x)
    positions = jnp.arange(start, start + s)
    q, k, v = L._project_qkv(p["attn"], h, spec, positions)
    ps = cache["k"].shape[1]
    with jax.named_scope("kv_write"):
        pages = table_row[jnp.asarray(
            [(start + j) // ps for j in range(s)])]
        offs = jnp.asarray([(start + j) % ps for j in range(s)],
                           jnp.int32)
        ck = cache["k"].at[pages, offs].set(k[0].astype(cache["k"].dtype))
        cv = cache["v"].at[pages, offs].set(v[0].astype(cache["v"].dtype))
        # same CPU-XLA bf16-hoisting workaround as attention_decode
        ckb, cvb = jax.lax.optimization_barrier((ck, cv))
    with jax.named_scope("attention"):
        n_hist = -(-(start + s) // ps)            # pages holding history
        hist = table_row[:n_hist]
        kf = ckb[hist].reshape(1, n_hist * ps, spec.n_kv_heads,
                               spec.head_dim)[:, :start + s]
        vf = cvb[hist].reshape(1, n_hist * ps, spec.n_kv_heads,
                               spec.head_dim)[:, :start + s]
        out = ops.attention(q, kf, vf, causal=True, window=spec.window,
                            q_offset=start)
    with jax.named_scope("attn_out"):
        x = ops.gemm(out.reshape(b, s, -1), p["attn"]["wo"], residual=x)
    cache = {"k": ck, "v": cv}
    hh = _norm(cfg, p["norm2"], x)
    if kind == "moe":
        y, _ = MOE.moe_ffn(p["moe"], hh, top_k=cfg.top_k,
                           capacity_factor=cfg.capacity_factor)
        x = x + y
    else:
        x = _mlp(cfg, p["mlp"], hh, residual=x)
    return x, cache


def prefill_paged_chunk(params: dict, cfg: ModelConfig,
                        tokens: jax.Array, cache: dict, slot: jax.Array,
                        table_row: jax.Array, start_pos: int
                        ) -> Tuple[jax.Array, dict]:
    """Prefill ONE chunk of a prompt into the paged cache.

    tokens: (1, s) — prompt positions [start_pos, start_pos + s);
    ``table_row``: the slot's TRUE (max_pages,) int32 table (the device
    ``cache["page_table"]`` row stays masked/sink until the engine
    promotes the slot after its last chunk, so interleaved decode
    bursts can't read a half-written prompt); ``start_pos`` is STATIC —
    one compiled chunk per (length, offset) pair.

    Prefix sharing enters here too: a prompt whose first ``start_pos``
    tokens ride cached shared pages prefills only its suffix, attending
    the shared history through ``table_row``.  Returns (last-position
    logits (1, V), updated cache) with ``pos[slot] = start_pos + s``.
    """
    assert tokens.shape[0] == 1, "chunk prefill admits one request"
    s = tokens.shape[1]
    slot = jnp.asarray(slot, jnp.int32)
    table_row = jnp.asarray(table_row, jnp.int32)
    x = L.embed(params["embed"], tokens)
    x = _maybe_abs_pos(cfg, x, start_pos)
    kinds = cfg.layer_pattern

    def unit(h, xs):
        p_unit, c_unit = xs
        new_c = {}
        for i, kind in enumerate(kinds):
            ck = f"u{i}"
            h, new_c[ck] = _prefill_chunk_layer(
                p_unit[ck], c_unit[ck], cfg, kind, h, table_row,
                start_pos)
        return h, new_c

    x, new_layer_cache = jax.lax.scan(
        unit, x, (params["layers"], cache["layers"]))
    new_cache = dict(cache, layers=new_layer_cache,
                     pos=cache["pos"].at[slot].set(start_pos + s))
    if cfg.tail_pattern:
        new_tail = {}
        for i, kind in enumerate(cfg.tail_pattern):
            tk = f"t{i}"
            x, new_tail[tk] = _prefill_chunk_layer(
                params["tail"][tk], cache["tail"][tk], cfg, kind, x,
                table_row, start_pos)
        new_cache["tail"] = new_tail
    x = _norm(cfg, params["final_norm"], x)
    logits = ops.gemm(x[:, -1], params["lm_head"], out_dtype=jnp.float32)
    return logits, new_cache


def copy_kv_pages(cache: dict, src: jax.Array, dst: jax.Array) -> dict:
    """Copy physical pages ``src[i] -> dst[i]`` on every paged K/V leaf
    (the copy-on-write primitive: a slot diverging mid-page gets its own
    copy of the shared page before it writes).  src/dst: (n,) int32."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)

    def one(path, leaf):
        keys = [str(p.key) for p in path
                if isinstance(p, jax.tree_util.DictKey)]
        if not keys or keys[-1] not in ("k", "v"):
            return leaf
        if keys[0] == "layers":            # stacked (repeats, pages, ...)
            return leaf.at[:, dst].set(leaf[:, src])
        return leaf.at[dst].set(leaf[src])

    return jax.tree_util.tree_map_with_path(one, cache)
