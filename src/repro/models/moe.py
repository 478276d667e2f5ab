"""Mixture-of-Experts FFN: sort-based dispatch + grouped ragged expert
GEMMs + shard_map expert parallelism.

Design notes (EP posture for kimi-k2's 384 experts / qwen3's 128):

* Routing: softmax -> top-k -> renormalized gates (standard token-choice).
* Dispatch (:func:`_sort_dispatch`): tokens are *sorted by expert* and
  packed **ragged** — a ``(t*k, d)`` buffer where expert ``e``'s rows
  occupy ``[start_e, start_e + size_e)`` with ``size_e =
  min(count_e, C)`` (capacity C per expert, overflow dropped — GShard
  capacity semantics).  No ``(T, E, C)`` one-hot tensor and no padded
  ``(E, C, d)`` compute buffer is ever materialized on the compute path.
* Expert compute (:func:`_expert_gemms`): ONE grouped ragged GEMM per
  projection (``ops.gemm_grouped`` — a single Pallas sweep over the
  concatenated groups against the stacked ``(E, d, f)`` bank), so the
  expert FLOPs are the *true routed rows*, not ``E*C`` dense capacity —
  the megablocks formulation, planned and billed by the same
  spec->plan->execute pipeline as every other GEMM in the model
  (``plan.explain()`` shows the per-group billing and the
  padding-FLOPs saving).  ``REPRO_MOE_GROUPED=0`` falls back to the
  padded dense einsum (:func:`_expert_gemms_dense`), kept as the A/B
  baseline and capacity-FLOPs reference.
* **EP path** (:func:`_moe_ffn_ep`, the default under a mesh): the
  dispatch runs inside ``shard_map`` — each device sorts its *local*
  tokens into per-expert send buffers and ONE tiled ``all_to_all`` over
  the 'model' axis delivers every expert its tokens; the per-source
  group sizes ride a second (tiny, ``(E, 1)`` int32) all_to_all so the
  receiver can compact its ``(E/m, m*C, d)`` recv buffer into the same
  ragged layout and run the same grouped GEMMs.  The combine is the
  mirror-image all_to_all.  This is what GSPMD cannot derive from the
  pjit scatter formulation (data-dependent scatter indices into an
  expert-sharded buffer force it to replicate the 150 GB dispatch
  buffer — measured 1.5 TB/device on kimi-k2 train_4k; the shard_map
  path is ~40x smaller and turns the collective term from broadcast
  all-gathers into the minimal token all-to-all).
* **pjit path** (:func:`_moe_ffn_pjit`): kept for decode steps (tiny
  token counts), meshless unit tests, and as the A/B baseline
  (``REPRO_MOE_EP=0``).

Expert banks may arrive quantized (``{"q": int8 (E,k,n), "scale": f32
(E,1,n)}`` from :func:`repro.quant.quantize_params`) — the grouped GEMM
dequantizes in-register per expert panel (W8A16); the dense fallback
and the dense oracle dequantize up front.

The load-balancing auxiliary loss (Switch-style) is returned alongside,
computed from the dispatch's own expert counts and psum-reduced over
the mesh on the EP path.  When telemetry is enabled the pjit path
emits ``moe.group_sizes`` (routed rows actually computed) and
``moe.dropped_tokens`` (capacity-dropped assignments) counters.
"""

from __future__ import annotations

import math
import os
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as shd
from repro import ops, quant, telemetry
from repro.models.layers import dense_init, _split


def init_moe(key, d: int, d_ff: int, n_experts: int, dtype) -> dict:
    k1, k2, k3, k4 = _split(key, 4)
    std_in = 1.0 / math.sqrt(d)
    std_out = 1.0 / math.sqrt(d_ff)

    def expert_init(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    return {
        "router": dense_init(k1, d, n_experts, jnp.float32),
        "w_gate": expert_init(k2, (n_experts, d, d_ff), std_in),
        "w_up": expert_init(k3, (n_experts, d, d_ff), std_in),
        "w_down": expert_init(k4, (n_experts, d_ff, d), std_out),
    }


def capacity(n_tokens: int, n_experts: int, top_k: int,
             factor: float = 1.25, multiple: int = 8) -> int:
    c = math.ceil(n_tokens * top_k * factor / n_experts)
    return max(multiple, ((c + multiple - 1) // multiple) * multiple)


def grouped_enabled() -> bool:
    """Grouped ragged expert GEMMs (default); ``REPRO_MOE_GROUPED=0``
    selects the padded dense-einsum baseline."""
    return os.environ.get("REPRO_MOE_GROUPED", "1") != "0"


def ep_enabled() -> bool:
    return os.environ.get("REPRO_MOE_EP", "1") != "0"


class MoeDispatch(NamedTuple):
    """Sort-based dispatch of ``t*k`` (token, expert) assignments.

    The assignment axis is sorted by expert (stable, so source order is
    preserved within each expert).  ``xs`` is the ragged pack: kept
    assignment ``i`` lives at row ``dest[i]`` — expert ``e``'s rows are
    ``[starts_e, starts_e + sizes[e])`` with the group starts the
    exclusive cumsum of ``sizes`` — and rows past ``sum(sizes)`` are
    zero.  Dropped assignments (position within their expert >= the
    capacity) have ``dest == t*k`` (out of range) and ``in_cap False``.
    """

    xs: jax.Array           # (t*k, d) ragged expert-sorted tokens
    sizes: jax.Array        # (E,) int32 kept rows per expert (<= capacity)
    counts: jax.Array       # (E,) int32 raw routed counts (pre-capacity)
    dest: jax.Array         # (t*k,) ragged row per assignment (t*k = drop)
    slot: jax.Array         # (t*k,) position within the expert group
    token_idx: jax.Array    # (t*k,) source token of each assignment
    order: jax.Array        # (t*k,) argsort permutation of flat ids
    in_cap: jax.Array       # (t*k,) bool — assignment kept
    sorted_e: jax.Array     # (t*k,) expert id, ascending


def _sort_dispatch(xe: jax.Array, top_ids: jax.Array, top_k: int,
                   n_experts: int, c: int) -> MoeDispatch:
    """Sort tokens by expert into the ragged ``(t*k, d)`` pack
    (overflow beyond capacity ``c`` dropped)."""
    t = xe.shape[0]
    flat_e = top_ids.reshape(-1)                               # (t*k,)
    order = jnp.argsort(flat_e)                                # stable
    sorted_e = flat_e[order]
    token_idx = order // top_k
    counts = jnp.bincount(flat_e, length=n_experts).astype(jnp.int32)
    starts = jnp.cumsum(counts) - counts                       # exclusive
    slot = jnp.arange(t * top_k, dtype=jnp.int32) - starts[sorted_e]
    in_cap = slot < c
    sizes = jnp.minimum(counts, c)
    rstarts = jnp.cumsum(sizes) - sizes                        # ragged
    # out-of-capacity entries get dest=t*k -> dropped by scatter 'drop'
    dest = jnp.where(in_cap, rstarts[sorted_e] + slot, t * top_k)
    xs = jnp.zeros((t * top_k, xe.shape[-1]), xe.dtype)
    xs = xs.at[dest].set(xe[token_idx], mode="drop")
    return MoeDispatch(xs, sizes, counts, dest, slot, token_idx, order,
                       in_cap, sorted_e)


def _route(xe: jax.Array, router: jax.Array, top_k: int):
    logits = ops.gemm(xe, router, out_dtype=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (t, E)
    gate_vals, top_ids = jax.lax.top_k(probs, top_k)           # (t, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)
    return probs, gate_vals, top_ids


def _aux_loss(counts: jax.Array, probs: jax.Array, n_tokens) -> jax.Array:
    """Switch-style load-balance loss ``E * sum_e f_e * p_e`` straight
    from the dispatch's expert counts (``f_e = counts_e / t`` — the same
    value the one-hot formulation computes, without re-materializing
    the (t, k, E) one-hot)."""
    n_experts = counts.shape[0]
    freq = counts.astype(jnp.float32) / n_tokens
    return n_experts * jnp.sum(freq * jnp.mean(probs, axis=0))


def _bank(w, dtype) -> jax.Array:
    """Dense view of an expert bank (dequantizes ``{"q","scale"}``)."""
    return quant.dequantize_weight(w, dtype) if quant.is_quantized(w) \
        else w


def _expert_gemms(params: dict, xs: jax.Array, sizes: jax.Array,
                  dtype, dense_rows: int = 0) -> jax.Array:
    """SwiGLU over the ragged expert-sorted rows: three grouped ragged
    GEMMs against the stacked banks (silu fused into the gate GEMM's
    epilogue).  Quantized banks stream int8 and dequantize in-register
    (W8A16).  ``dense_rows`` is the E*C capacity row count the padded
    formulation would compute — plan-level billing context only."""
    dr = dense_rows or None
    gate = ops.gemm_grouped(xs, params["w_gate"], sizes,
                            activation="silu", out_dtype=dtype,
                            dense_rows=dr)
    up = ops.gemm_grouped(xs, params["w_up"], sizes, out_dtype=dtype,
                          dense_rows=dr)
    h = gate * up
    return ops.gemm_grouped(h, params["w_down"], sizes, out_dtype=dtype,
                            dense_rows=dr)


def _expert_gemms_dense(params: dict, buf: jax.Array, dtype) -> jax.Array:
    """Padded dense-capacity baseline: batched einsum over (E, C, d)."""
    w_gate = _bank(params["w_gate"], dtype)
    w_up = _bank(params["w_up"], dtype)
    w_down = _bank(params["w_down"], dtype)
    gate = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    up = jnp.einsum("ecd,edf->ecf", buf, w_up)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(dtype) * up
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _emit_moe_counters(n_assignments: int, sizes: jax.Array) -> None:
    """``moe.group_sizes`` (rows actually routed through the grouped
    GEMMs) and ``moe.dropped_tokens`` (capacity-dropped assignments) —
    host counters fed by a debug callback, trace-time gated on
    :func:`repro.telemetry.enabled`."""
    if not telemetry.enabled():
        return

    def cb(kept):
        rec = telemetry.recorder()
        if rec is not None:
            rec.counter("moe.group_sizes").add(int(kept))
            rec.counter("moe.dropped_tokens").add(
                n_assignments - int(kept))

    jax.debug.callback(cb, jnp.sum(sizes))


def moe_ffn(params: dict, x: jax.Array, *, top_k: int,
            capacity_factor: float = 1.25
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (b, s, d) -> (y: (b, s, d), aux_loss: scalar).

    Dispatches to the shard_map EP path when a mesh with a non-trivial
    'model' axis is active and shapes divide; else the pjit path.
    """
    mesh = shd.current_mesh()
    n_experts = params["router"].shape[-1]
    if mesh is not None and ep_enabled():
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        m = sizes.get("model", 1)
        batch_axes = tuple(a for a in ("pod", "data")
                           if a in mesh.axis_names)
        bsz = 1
        for a in batch_axes:
            bsz *= sizes[a]
        b, s, _ = x.shape
        if (m > 1 and n_experts % m == 0 and b % bsz == 0
                and s % m == 0 and (b // bsz) * (s // m) >= 1):
            return _moe_ffn_ep(params, x, top_k=top_k,
                               capacity_factor=capacity_factor,
                               mesh=mesh, batch_axes=batch_axes)
    return _moe_ffn_pjit(params, x, top_k=top_k,
                         capacity_factor=capacity_factor)


def _ep_grouped_gemms(params: dict, recv: jax.Array, sz: jax.Array,
                      c: int, dtype) -> jax.Array:
    """Grouped expert GEMMs on one EP shard's recv buffer.

    ``recv`` is the (E_loc, n_src*c, d) all_to_all product — each local
    expert's tokens arrive as n_src chunks of capacity c with
    ``sz[e, src]`` live rows each.  Compact into the ragged layout
    (one scatter), run the same grouped GEMMs as the pjit path with
    group sizes summed over sources, and scatter back to the dense
    chunk layout the mirror all_to_all expects.
    """
    e_loc, n_src = sz.shape
    d = recv.shape[-1]
    rows = e_loc * n_src * c
    gsize = jnp.sum(sz, axis=1).astype(jnp.int32)              # (E_loc,)
    gstart = jnp.cumsum(gsize) - gsize
    src_off = jnp.cumsum(sz, axis=1) - sz                      # (E_loc, n_src)
    i = jnp.arange(c, dtype=jnp.int32)
    dest = gstart[:, None, None] + src_off[:, :, None] + i[None, None, :]
    valid = i[None, None, :] < sz[:, :, None]
    dest = jnp.where(valid, dest, rows).reshape(rows)          # drop dead
    xs = jnp.zeros((rows, d), dtype).at[dest].set(
        recv.reshape(rows, d), mode="drop")
    ys = _expert_gemms(params, xs, gsize, dtype, dense_rows=rows)
    out = jnp.where(valid.reshape(rows, 1),
                    ys[jnp.minimum(dest, rows - 1)], 0)
    return out.reshape(e_loc, n_src * c, d)


def _moe_ffn_ep(params: dict, x: jax.Array, *, top_k: int,
                capacity_factor: float, mesh, batch_axes
                ) -> Tuple[jax.Array, jax.Array]:
    """shard_map EP: local sort-dispatch + one tiled all_to_all each way.

    Per device: local tokens t_loc = (b/|batch|)·(s/|model|); send buffer
    (E, C_src, d) with per-source-shard capacity C_src; the tiled
    all_to_all over 'model' yields (E/m, m·C_src, d) — every local expert
    sees its tokens from all sources — and the per-source kept counts
    ride an (E, 1) int32 all_to_all alongside so the receiver can pack
    the chunks ragged for the grouped expert GEMMs.  Weights enter with
    full d/f per device (the boundary all-gather is FSDP's per-layer
    unshard, same traffic GSPMD emits).
    """
    n_experts = params["router"].shape[-1]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    m = sizes["model"]
    all_axes = tuple(batch_axes) + ("model",)

    def local(w_gate, w_up, w_down, router, x_loc):
        b_loc, s_loc, d = x_loc.shape
        t_loc = b_loc * s_loc
        xe = x_loc.reshape(t_loc, d)
        probs, gate_vals, top_ids = _route(xe, router, top_k)
        c_src = capacity(t_loc, n_experts, top_k, capacity_factor)
        dsp = _sort_dispatch(xe, top_ids, top_k, n_experts, c_src)
        slot_c = jnp.where(dsp.in_cap, dsp.slot, c_src)
        buf = jnp.zeros((n_experts, c_src, d), x_loc.dtype)
        buf = buf.at[dsp.sorted_e, slot_c].set(xe[dsp.token_idx],
                                               mode="drop")

        # (E, C, d) -> (E/m, m*C, d): one tiled all_to_all over 'model'
        recv = jax.lax.all_to_all(buf, "model", split_axis=0,
                                  concat_axis=1, tiled=True)
        eparams = {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        if grouped_enabled():
            sz = jax.lax.all_to_all(
                dsp.sizes.reshape(n_experts, 1), "model",
                split_axis=0, concat_axis=1, tiled=True)       # (E/m, m)
            out_loc = _ep_grouped_gemms(eparams, recv, sz, c_src,
                                        x_loc.dtype)
        else:
            out_loc = _expert_gemms_dense(eparams, recv, x_loc.dtype)
        # mirror: (E/m, m*C, d) -> (E, C, d) back at the source shard
        back = jax.lax.all_to_all(out_loc, "model", split_axis=1,
                                  concat_axis=0, tiled=True)

        gathered = back[dsp.sorted_e, slot_c]                  # (t*k, d)
        weights = (gate_vals.reshape(-1)[dsp.order]
                   * dsp.in_cap.astype(jnp.float32)).astype(x_loc.dtype)
        y = jnp.zeros((t_loc, d), x_loc.dtype).at[dsp.token_idx].add(
            gathered * weights[:, None])

        # global Switch aux loss: psum sums over every mesh axis
        freq_sum = dsp.counts.astype(jnp.float32)
        prob_sum = jnp.sum(probs, axis=0)
        n = jnp.float32(t_loc)
        for ax in all_axes:
            freq_sum = jax.lax.psum(freq_sum, ax)
            prob_sum = jax.lax.psum(prob_sum, ax)
            n = jax.lax.psum(n, ax)
        aux = n_experts * jnp.sum((freq_sum / n) * (prob_sum / n))
        return y.reshape(b_loc, s_loc, d), aux

    batch_spec = batch_axes if batch_axes else None
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("model", None, None), P("model", None, None),
                  P("model", None, None), P(),
                  P(batch_spec, "model", None)),
        out_specs=(P(batch_spec, "model", None), P()),
        check_vma=False)
    return fn(params["w_gate"], params["w_up"], params["w_down"],
              params["router"], x)


def _moe_ffn_pjit(params: dict, x: jax.Array, *, top_k: int,
                  capacity_factor: float = 1.25
                  ) -> Tuple[jax.Array, jax.Array]:
    """x: (b, s, d) -> (y: (b, s, d), aux_loss: scalar)."""
    b, s, d = x.shape
    t = b * s
    n_experts = params["router"].shape[-1]
    c = capacity(t, n_experts, top_k, capacity_factor)
    xe = x.reshape(t, d)

    probs, gate_vals, top_ids = _route(xe, params["router"], top_k)
    dsp = _sort_dispatch(xe, top_ids, top_k, n_experts, c)
    aux = _aux_loss(dsp.counts, probs, t)
    _emit_moe_counters(t * top_k, dsp.sizes)

    if grouped_enabled():
        # ragged grouped expert GEMMs over the true routed rows
        ys = _expert_gemms(params, dsp.xs, dsp.sizes, x.dtype,
                           dense_rows=n_experts * c)
        gathered = ys[jnp.minimum(dsp.dest, t * top_k - 1)]    # (t*k, d)
    else:
        # dense-capacity baseline: padded (E, C, d) buffer + einsum
        slot_c = jnp.where(dsp.in_cap, dsp.slot, c)
        buf = jnp.zeros((n_experts, c, d), x.dtype)
        buf = buf.at[dsp.sorted_e, slot_c].set(xe[dsp.token_idx],
                                               mode="drop")
        buf = shd.act(buf, ("expert", None, None))
        out = _expert_gemms_dense(params, buf, x.dtype)
        out = shd.act(out, ("expert", None, None))
        gathered = out[dsp.sorted_e, slot_c]                   # (t*k, d)

    weights = (gate_vals.reshape(-1)[dsp.order]
               * dsp.in_cap.astype(jnp.float32)).astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[dsp.token_idx].add(
        gathered * weights[:, None])
    return y.reshape(b, s, d), aux


def moe_ffn_dense_ref(params: dict, x: jax.Array, *, top_k: int
                      ) -> jax.Array:
    """Dense oracle: every expert computed for every token, combined with
    the same renormalized top-k gates, no capacity drops.  Used by tests
    to validate the sort-dispatch path (with capacity_factor high enough
    that nothing drops).  Quantized expert banks are dequantized up
    front, so it also oracles the W8A16 grouped path at einsum
    tolerance."""
    b, s, d = x.shape
    xe = x.reshape(b * s, d)
    logits = xe.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, top_ids = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)
    n_experts = params["router"].shape[-1]
    combine = jnp.zeros_like(probs).at[
        jnp.arange(xe.shape[0])[:, None], top_ids].set(gate_vals)

    w_gate = _bank(params["w_gate"], x.dtype)
    w_up = _bank(params["w_up"], x.dtype)
    w_down = _bank(params["w_down"], x.dtype)
    gate = jnp.einsum("td,edf->tef", xe, w_gate)
    up = jnp.einsum("td,edf->tef", xe, w_up)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    out = jnp.einsum("tef,efd->ted", h, w_down)
    y = jnp.einsum("ted,te->td", out.astype(jnp.float32), combine)
    return y.astype(x.dtype).reshape(b, s, d)
