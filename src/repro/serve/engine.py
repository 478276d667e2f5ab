"""Continuous-batching decode engine: per-slot KV positions from the
scheduler down to the flash-decode kernel.

The old engine was a lockstep static batch — one scalar ``cache["pos"]``
shared by every sequence, so a single long request held its whole batch
hostage and short prompts padded to the longest.  This engine is a
scheduler over a fixed pool of cache *slots*:

* a request queue feeds a :class:`SlotScheduler` (pure-host allocator,
  property-tested in isolation);
* admission prefills ONE request into a free slot of the live cache
  (:func:`repro.models.transformer.prefill_into_slot` — resident slots
  are untouched, ``jax.lax.dynamic_update_*`` on every cache leaf);
* every batched ``decode_step`` advances all slots at their own
  positions (the ``(b,)`` ``cache["pos"]`` contract, masked per-row all
  the way down to the flash-decode kernel);
* per-slot sampling params (temperature / eos / max_tokens), per-slot
  completion + eviction, and rolling tokens/sec + slot-occupancy
  metrics.

Host syncs are amortized: decode runs in bursts of up to
``EOS_CHECK_EVERY`` steps (bounded by the tightest remaining
``max_tokens``, so length-based completions are exact); EOS is detected
at burst boundaries and any tokens sampled after it are masked before a
result is returned.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.configs.base import ModelConfig
from repro.core import bandwidth
from repro.models import transformer as T
from repro.serve import paging


# EOS completion is checked on the host only every this-many steps: a
# per-token ``bool(done)`` would force a device->host sync every decode
# step and serialize the jitted step stream.  Bursts are additionally
# capped by the smallest remaining max_tokens among active slots, so
# length-based completions (and the admissions they unblock) land on
# the exact step; the trade is up to EOS_CHECK_EVERY-1 wasted (masked)
# steps after an EOS.
EOS_CHECK_EVERY = 8


#: the ragged acceptance trace — (prompt_len, max_tokens) pairs — that
#: tests/test_serve.py and benchmarks/serve_bench.py both pin: every
#: request must decode bit-identically to a solo batch-1 greedy run
ACCEPTANCE_TRACE = ((4, 8), (16, 32), (8, 16), (32, 4))


def acceptance_requests(vocab: int, seed: int = 0) -> List["Request"]:
    """Materialize the acceptance trace as requests (shared by
    tests/test_serve.py and benchmarks/serve_bench.py so both always
    exercise the same trace)."""
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, vocab, (p,))
                    .astype(np.int32), max_tokens=mt)
            for p, mt in ACCEPTANCE_TRACE]


def solo_greedy(params, cfg: ModelConfig, prompt: np.ndarray,
                max_tokens: int, max_len: int) -> np.ndarray:
    """The parity oracle: one request alone at batch 1, greedy —
    prefill then token-by-token decode.  The continuous engine must
    reproduce this bit-for-bit for greedy requests."""
    cache = T.init_cache(cfg, 1, max_len)
    logits, cache = T.prefill(params, cfg,
                              jnp.asarray(prompt[None], jnp.int32),
                              cache)
    step = jax.jit(lambda t, c: T.decode_step(params, cfg, t, c))
    toks = []
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    for _ in range(max_tokens):
        toks.append(int(tok[0, 0]))
        logits, cache = step(tok, cache)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    return np.asarray(toks, np.int32)


@dataclasses.dataclass
class Request:
    """One generation request (host-side)."""
    prompt: np.ndarray                   # (s,) int32 prompt token ids
    max_tokens: int
    temperature: float = 0.0
    eos_id: Optional[int] = None
    arrival: float = 0.0                 # seconds since trace start
    frames: Optional[np.ndarray] = None  # (F, d) audio stub frames
    rid: int = -1                        # assigned by submit()


@dataclasses.dataclass
class RequestResult:
    rid: int
    prompt_len: int
    tokens: np.ndarray                   # generated ids (EOS-terminated)
    admitted_step: int                   # engine decode-step counters
    finished_step: int
    arrival: float                       # request arrival (trace clock)
    admitted_time: float                 # same clock as arrival when the
    finished_time: float                 # ... trace supplies one
    queue_wait: float = 0.0              # arrival -> admission seconds
    ttft: float = 0.0                    # arrival -> first sampled token
    prefill_chunks: int = 1              # chunked-prefill admissions > 1

    @property
    def n_tokens(self) -> int:
        return int(self.tokens.shape[0])


@dataclasses.dataclass
class GenerationResult:
    """Compat result for :meth:`DecodeEngine.generate`."""
    tokens: np.ndarray                   # (b, steps) generated ids
    steps: int


class SlotScheduler:
    """Pure-host slot allocator: FIFO request queue over ``n_slots``
    cache slots.  No device state — the invariants (every queued request
    is admitted exactly once, a slot never serves two live requests) are
    property-tested in isolation (tests/test_serve.py)."""

    def __init__(self, n_slots: int):
        assert n_slots >= 1
        self.n_slots = n_slots
        self.queue: Deque[int] = collections.deque()
        self.slot_rid: List[Optional[int]] = [None] * n_slots
        self._free: List[int] = list(range(n_slots))

    @property
    def n_active(self) -> int:
        return self.n_slots - len(self._free)

    @property
    def active_slots(self) -> List[int]:
        return [s for s, r in enumerate(self.slot_rid) if r is not None]

    def has_work(self) -> bool:
        return bool(self.queue) or self.n_active > 0

    def submit(self, rid: int) -> None:
        self.queue.append(rid)

    def admit(self) -> Optional[tuple]:
        """Pop (slot, rid) when a slot is free and a request is queued;
        None otherwise.  Lowest free slot first (deterministic)."""
        if not self.queue or not self._free:
            return None
        slot = min(self._free)
        self._free.remove(slot)
        rid = self.queue.popleft()
        assert self.slot_rid[slot] is None, "slot double-booked"
        self.slot_rid[slot] = rid
        return slot, rid

    def release(self, slot: int) -> int:
        rid = self.slot_rid[slot]
        assert rid is not None, "releasing a free slot"
        self.slot_rid[slot] = None
        self._free.append(slot)
        return rid


@dataclasses.dataclass
class _SlotState:
    """Host-side decode state of one occupied slot."""
    req: Request
    gen: List[int]                       # synced generated token ids
    first_dev: Optional[jax.Array]       # prefill-sampled token (device)
    remaining: int                       # decode steps left (max_tokens-1
    admitted_step: int                   # ... minus steps already run)
    admitted_time: float
    queue_wait: float = 0.0              # arrival -> admission seconds
    first_token_time: float = 0.0        # first token ready (run clock)
    admitted_abs: float = 0.0            # perf_counter absolutes for the
    first_abs: float = 0.0               # ... telemetry lifecycle spans
    prefill_chunks: int = 1              # admission chunks (paged mode)
    pos: int = 0                         # cache position (KV billing)


@dataclasses.dataclass
class _PrefillState:
    """A paged slot mid-admission: its prompt lands in fixed-size chunks
    interleaved with decode bursts, and the slot only joins the decode
    batch (device page-table row unmasked, first token sampled) after
    the last chunk."""
    req: Request
    row: np.ndarray                      # true (max_pages,) page table
    next_pos: int                        # prompt positions written so far
    chunks: int
    admitted_time: float
    admitted_abs: float
    queue_wait: float


class DecodeEngine:
    """Continuous-batching serving engine.

    ``batch`` is the slot-pool size (kept under its legacy name — each
    slot is one resident sequence of the live cache); ``temperature`` /
    ``eos_id`` are engine-level defaults that per-request values
    override.

    ``page_size`` switches the KV cache from dense per-slot rows to a
    block-paged pool (``max_len`` rounds up to a page multiple):
    ``n_pages`` sizes the pool (default: dense-equivalent capacity,
    ``slots * max_len / page_size`` plus the reserved sink page),
    ``prefill_chunk`` splits admissions into fixed-token chunks
    interleaved with decode bursts, and ``prefix_cache`` enables
    content-hash prefix sharing (shared prompts prefill once;
    copy-on-write on the first divergent mid-page append).
    """

    def __init__(self, params, cfg: ModelConfig, *, batch: int,
                 max_len: int, temperature: float = 0.0,
                 eos_id: Optional[int] = None,
                 page_size: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = True):
        self.params = params
        self.cfg = cfg
        self.n_slots = self.batch = batch
        self.temperature = temperature
        self.eos_id = eos_id
        self.paged = page_size is not None
        self.page_size = page_size
        self.prefill_chunk = prefill_chunk
        if self.paged:
            # only attn-family stacks page: recurrent state has no
            # page-table indirection, so chunked prefill would reuse
            # stale slot state, decode bursts would mutate mid-prefill
            # recurrence (only attention writes are sink-masked), and
            # prefix sharing can't skip tokens through a recurrence
            bad = sorted({k for k in cfg.all_kinds
                          if k in ("ssm", "rec")})
            if bad:
                raise ValueError(
                    f"paged engine: recurrent layer kinds {bad} "
                    f"unsupported (arch {cfg.name}); use the dense "
                    "engine")
            if cfg.encoder_layers:
                raise ValueError("paged engine: encoder-decoder archs "
                                 "unsupported")
            # gathered-table length == dense max_len keeps the paged
            # reductions operand-for-operand identical to the dense
            # layout (the bit-parity contract); round up, never down
            max_len = -(-max_len // page_size) * page_size
        self.max_len = max_len

        self._prefill_slot = jax.jit(
            lambda p, toks, cache, slot, frames: T.prefill_into_slot(
                p, cfg, toks, cache, slot, max_len=max_len,
                frames=frames),
            donate_argnums=(2,))
        self._step = jax.jit(
            lambda p, tok, cache: T.decode_step(p, cfg, tok, cache),
            donate_argnums=(2,))
        self._argmax = jax.jit(self._argmax_impl)
        self._sample_temp = jax.jit(self._sample_temp_impl)

        self.kv: Optional[paging.PagedKV] = None
        self._prefilling: Dict[int, _PrefillState] = {}
        if self.paged:
            max_pages = max_len // page_size
            if n_pages is None:
                n_pages = 1 + self.n_slots * max_pages
            self.kv = paging.PagedKV(self.n_slots, n_pages, page_size,
                                     max_pages,
                                     prefix_cache=prefix_cache)
            self._table_np = np.full((self.n_slots, max_pages),
                                     paging.SINK_PAGE, np.int32)
            # prompt chunks compile per (length, start): the static
            # start makes the chunk's page-scatter indices and its
            # exact-length history slice compile-time, which is what
            # keeps chunked prefill bit-identical to a whole-prompt one
            self._prefill_chunk_fn = jax.jit(
                lambda p, toks, cache, slot, row, start:
                    T.prefill_paged_chunk(p, cfg, toks, cache, slot,
                                          row, start),
                static_argnums=(5,), donate_argnums=(2,))
            self._copy_pages = jax.jit(
                lambda cache, src, dst: T.copy_kv_pages(cache, src, dst),
                donate_argnums=(0,))

        self._requests: Dict[int, Request] = {}
        self._sched = SlotScheduler(self.n_slots)
        self._state: Dict[int, _SlotState] = {}      # slot -> state
        self._next_rid = 0
        self._cache = None
        self._tok = jnp.zeros((self.n_slots, 1), jnp.int32)
        self._temps = np.zeros((self.n_slots,), np.float32)
        self._key = jax.random.PRNGKey(0)
        self.reset_metrics()

    # ------------------------------------------------------------ sampling

    @staticmethod
    def _argmax_impl(logits):
        with jax.named_scope("sample"):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    @staticmethod
    def _sample_temp_impl(logits, key, temps):
        """Per-slot sampling: greedy rows where temperature == 0,
        categorical at ``logits / temp`` elsewhere — one batched op."""
        with jax.named_scope("sample"):
            greedy = jnp.argmax(logits, axis=-1)
            scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
            sampled = jax.random.categorical(key, scaled, axis=-1)
            return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)

    def _sample(self, logits: jax.Array, temps: np.ndarray) -> jax.Array:
        """logits: (n, V) -> (n,) int32 tokens."""
        if not (temps > 0).any():
            return self._argmax(logits)
        self._key, sub = jax.random.split(self._key)
        return self._sample_temp(logits, sub, jnp.asarray(temps))

    # ------------------------------------------------------------- metrics

    def reset_metrics(self) -> None:
        self.metrics = {
            "decode_steps": 0,           # batched decode_step calls
            "useful_slot_steps": 0,      # sum over steps of active slots
            "prefill_tokens": 0,         # exact prompt tokens prefilled
            "generated_tokens": 0,       # tokens in returned results
            "completed": 0,
            "decode_time": 0.0,          # wall seconds inside bursts
            "prefill_chunks": 0,         # admission chunks across reqs
            # longest run of prompt tokens prefilled while >= 1
            # decode-ready slot sat waiting — the stall chunking bounds
            "max_prefill_stall_tokens": 0,
            "prefix_hits": 0,
            "prefix_misses": 0,
            "shared_prompt_tokens": 0,   # prompt tokens never prefilled
            # decode KV traffic billed at true per-row positions
            # (page-rounded when paged) vs what dense max_len rows
            # stream — the honest-accounting satellite
            "modeled_kv_bytes": 0,
            "modeled_kv_bytes_dense_rows": 0,
        }
        self._stall_run = 0

    def occupancy(self) -> float:
        """Mean fraction of slots serving a live request per decode
        step — the utilization the lockstep engine wasted."""
        steps = self.metrics["decode_steps"]
        if steps == 0:
            return 0.0
        return self.metrics["useful_slot_steps"] / (steps * self.n_slots)

    def tokens_per_sec(self) -> float:
        """Rolling decode throughput (generated tokens over wall time
        spent in decode bursts; prefill + jit compile excluded)."""
        t = self.metrics["decode_time"]
        return self.metrics["generated_tokens"] / t if t > 0 else 0.0

    # ----------------------------------------------------------- lifecycle

    def submit(self, req: Request) -> int:
        """Queue a request; returns its rid (admission order = FIFO)."""
        # the last generated token is sampled but never written back, so
        # a request occupies cache positions 0..prompt+max_tokens-2;
        # past max_len the per-row write clamps (silently overwriting
        # the last slot) while the mask keeps admitting the whole cache
        # — reject instead of decoding garbage
        need = int(req.prompt.shape[0]) + req.max_tokens - 1
        if need > self.max_len:
            raise ValueError(
                f"request needs {need} cache positions "
                f"(prompt {int(req.prompt.shape[0])} + max_tokens "
                f"{req.max_tokens} - 1) but the engine was built with "
                f"max_len={self.max_len}")
        if self.paged:
            if req.frames is not None:
                # reject here, not at admission inside the serve loop —
                # a bad request must not crash a mid-trace run
                raise ValueError("paged engine: audio/enc-dec requests "
                                 "unsupported")
            total = self.kv.total_pages(need)
            cap = self.kv.pool.n_pages - 1
            if total > cap:
                raise ValueError(
                    f"request needs {total} pages but the pool only has "
                    f"{cap} allocatable pages")
        rid = self._next_rid
        self._next_rid += 1
        req.rid = rid
        self._requests[rid] = req
        self._sched.submit(rid)
        telemetry.event("serve.request.queued", rid=rid,
                        prompt_len=int(req.prompt.shape[0]),
                        max_tokens=req.max_tokens, arrival=req.arrival)
        return rid

    def _ensure_cache(self) -> None:
        if self._cache is None:
            if self.paged:
                self._cache = T.init_paged_cache(
                    self.cfg, self.n_slots, self.kv.pool.n_pages,
                    self.page_size, self.kv.max_pages)
            else:
                self._cache = T.init_cache(self.cfg, self.n_slots,
                                           self.max_len)

    def _update_page_gauges(self) -> None:
        telemetry.gauge("serve.kv_pages_used").set(self.kv.pool.n_used)
        telemetry.gauge("serve.kv_pages_free").set(self.kv.pool.n_free)

    def _note_prefill_stall(self, n_tokens: int) -> None:
        """Account ``n_tokens`` of prefill work done while at least one
        decode-ready slot sat waiting (the stall chunked prefill
        bounds); a decode burst resets the running stall."""
        if self._state:
            self._stall_run += n_tokens
            self.metrics["max_prefill_stall_tokens"] = max(
                self.metrics["max_prefill_stall_tokens"],
                self._stall_run)

    def _admit(self, slot: int, req: Request,
               clock: Callable[[], float]) -> None:
        """Prefill the request into ``slot`` of the live cache and seed
        its first sampled token.  The first token is synced here —
        admission IS the time-to-first-token boundary, so its timestamp
        must not drift into the next decode burst."""
        plen = int(req.prompt.shape[0])
        adm_time = clock()
        adm_abs = time.perf_counter()
        queue_wait = max(adm_time - req.arrival, 0.0)
        toks = jnp.asarray(req.prompt[None, :], jnp.int32)
        frames = None if req.frames is None \
            else jnp.asarray(req.frames[None])
        with telemetry.span("serve.prefill", rid=req.rid, slot=slot,
                            prompt_len=plen) as sp:
            logits, self._cache = self._prefill_slot(
                self.params, toks, self._cache,
                jnp.asarray(slot, jnp.int32), frames)
            temp = np.float32(req.temperature)
            first = self._sample(logits, temp[None])     # (1,)
            sp.sync(first)
        jax.block_until_ready(first)
        first_time = clock()
        self._tok = self._tok.at[slot, 0].set(first[0])
        self._temps[slot] = temp
        self.metrics["prefill_tokens"] += plen
        self.metrics["prefill_chunks"] += 1
        self._note_prefill_stall(plen)
        telemetry.counter("serve.prefill_tokens").add(plen)
        telemetry.event("serve.request.admitted", rid=req.rid, slot=slot,
                        queue_wait=queue_wait,
                        step=self.metrics["decode_steps"])
        self._state[slot] = _SlotState(
            req=req, gen=[], first_dev=first[0],
            remaining=req.max_tokens - 1,
            admitted_step=self.metrics["decode_steps"],
            admitted_time=adm_time, queue_wait=queue_wait,
            first_token_time=first_time, admitted_abs=adm_abs,
            first_abs=time.perf_counter(), pos=plen)

    def _admit_paged(self, slot: int, req: Request,
                     clock: Callable[[], float]) -> None:
        """Map pages for the request and stage its prompt for chunked
        prefill.  Nothing is computed here beyond a possible
        copy-on-write page duplication; the slot joins the decode batch
        when :meth:`_run_prefill_chunk` lands its last chunk."""
        if req.frames is not None:
            raise ValueError("paged engine: audio/enc-dec requests "
                             "unsupported")
        plen = int(req.prompt.shape[0])
        adm_time = clock()
        adm_abs = time.perf_counter()
        queue_wait = max(adm_time - req.arrival, 0.0)
        need = plen + req.max_tokens - 1
        plan = self.kv.admit(slot, req.prompt, need)
        if plan.cow_src:
            self._cache = self._copy_pages(
                self._cache, jnp.asarray(plan.cow_src, jnp.int32),
                jnp.asarray(plan.cow_dst, jnp.int32))
        if self.kv.prefix is not None:
            if plan.prefix_hit:
                self.metrics["prefix_hits"] += 1
                telemetry.counter("serve.prefix_cache.hits").add(1)
            else:
                self.metrics["prefix_misses"] += 1
                telemetry.counter("serve.prefix_cache.misses").add(1)
            self.metrics["shared_prompt_tokens"] += plan.shared_tokens
        self._update_page_gauges()
        telemetry.event("serve.request.admitted", rid=req.rid, slot=slot,
                        queue_wait=queue_wait,
                        step=self.metrics["decode_steps"],
                        pages=plan.n_pages,
                        shared_tokens=plan.shared_tokens)
        self._prefilling[slot] = _PrefillState(
            req=req, row=self.kv.table_row(slot),
            next_pos=plan.shared_tokens, chunks=0,
            admitted_time=adm_time, admitted_abs=adm_abs,
            queue_wait=queue_wait)

    def _run_prefill_chunk(self, clock: Callable[[], float]
                           ) -> Optional[RequestResult]:
        """Land ONE prompt chunk for the oldest mid-prefill slot.  On
        the final chunk: sample the first token (the TTFT boundary),
        unmask the slot's device page-table row, publish its prompt
        pages to the prefix cache, and promote it to the decode batch.
        Returns a result only for max_tokens <= 1 requests, which
        finish at promotion."""
        slot = next(iter(self._prefilling))
        st = self._prefilling[slot]
        req = st.req
        plen = int(req.prompt.shape[0])
        csize = self.prefill_chunk or (plen - st.next_pos)
        chunk = req.prompt[st.next_pos:st.next_pos + csize]
        s = int(chunk.shape[0])
        with telemetry.span("serve.prefill_chunk", rid=req.rid,
                            slot=slot, start=st.next_pos, tokens=s):
            logits, self._cache = self._prefill_chunk_fn(
                self.params, jnp.asarray(chunk[None, :], jnp.int32),
                self._cache, jnp.asarray(slot, jnp.int32),
                jnp.asarray(st.row), int(st.next_pos))
        st.next_pos += s
        st.chunks += 1
        self.metrics["prefill_tokens"] += s
        telemetry.counter("serve.prefill_tokens").add(s)
        self._note_prefill_stall(s)
        if st.next_pos < plen:
            return None

        temp = np.float32(req.temperature)
        first = self._sample(logits, temp[None])
        jax.block_until_ready(first)
        first_time = clock()
        del self._prefilling[slot]
        self._tok = self._tok.at[slot, 0].set(first[0])
        self._temps[slot] = temp
        self._table_np[slot] = st.row
        self._cache["page_table"] = jnp.asarray(self._table_np)
        self.kv.register_prefix(slot, req.prompt)
        self.metrics["prefill_chunks"] += st.chunks
        self._update_page_gauges()
        self._state[slot] = _SlotState(
            req=req, gen=[], first_dev=first[0],
            remaining=req.max_tokens - 1,
            admitted_step=self.metrics["decode_steps"],
            admitted_time=st.admitted_time, queue_wait=st.queue_wait,
            first_token_time=first_time, admitted_abs=st.admitted_abs,
            first_abs=time.perf_counter(), prefill_chunks=st.chunks,
            pos=plen)
        if req.max_tokens <= 1:
            self._sync_slot(slot, None, None)
            return self._finish(slot, clock())
        return None

    def _finish(self, slot: int, now: float) -> RequestResult:
        """Truncate at EOS / max_tokens, emit the result, free the slot
        (and drop the engine's reference to the request — a long-lived
        engine must not accumulate served prompts/results).

        Tokens sampled after EOS (a slot keeps stepping until the burst
        boundary) are dropped here — a result never contains post-EOS
        garbage."""
        st = self._state.pop(slot)
        req = st.req
        toks = st.gen[:req.max_tokens]
        eos = req.eos_id
        if eos is not None and eos in toks:
            toks = toks[:toks.index(eos) + 1]
        self._temps[slot] = 0.0
        self._sched.release(slot)
        if self.paged:
            self.kv.release(slot)
            self._table_np[slot] = paging.SINK_PAGE
            self._cache["page_table"] = jnp.asarray(self._table_np)
            self._update_page_gauges()
        self._requests.pop(req.rid, None)
        self.metrics["generated_tokens"] += len(toks)
        self.metrics["completed"] += 1
        ttft = max(st.first_token_time - req.arrival, 0.0)
        if telemetry.enabled():
            fin_abs = time.perf_counter()
            arr_abs = st.admitted_abs - st.queue_wait
            common = dict(tid=req.rid, rid=req.rid)
            telemetry.complete_span("serve.request", arr_abs, fin_abs,
                                    prompt_len=int(req.prompt.shape[0]),
                                    n_tokens=len(toks), ttft=ttft,
                                    queue_wait=st.queue_wait, **common)
            telemetry.complete_span("serve.request.queued", arr_abs,
                                    st.admitted_abs, **common)
            telemetry.complete_span("serve.request.prefill",
                                    st.admitted_abs, st.first_abs,
                                    **common)
            telemetry.complete_span("serve.request.decode", st.first_abs,
                                    fin_abs, tokens=len(toks),
                                    attn_plan=self._attn_plan_key(),
                                    **common)
            telemetry.event("serve.request.finished", rid=req.rid,
                            n_tokens=len(toks), ttft=ttft,
                            queue_wait=st.queue_wait,
                            e2e=max(now - req.arrival, 0.0))
            telemetry.counter("serve.generated_tokens").add(len(toks))
            telemetry.counter("serve.completed").add(1)
        return RequestResult(
            rid=req.rid, prompt_len=int(req.prompt.shape[0]),
            tokens=np.asarray(toks, np.int32),
            admitted_step=st.admitted_step,
            finished_step=self.metrics["decode_steps"],
            arrival=req.arrival,
            admitted_time=st.admitted_time, finished_time=now,
            queue_wait=st.queue_wait, ttft=ttft,
            prefill_chunks=st.prefill_chunks)

    def _sync_slot(self, slot: int, burst_host: Optional[np.ndarray],
                   col: Optional[int]) -> None:
        """Pull this burst's tokens for one slot into host state."""
        st = self._state[slot]
        if st.first_dev is not None:
            st.gen.append(int(st.first_dev))
            st.first_dev = None
        if burst_host is not None:
            st.gen.extend(int(t) for t in burst_host[:, col])

    def _slot_done(self, slot: int) -> bool:
        st = self._state[slot]
        if len(st.gen) >= st.req.max_tokens:
            return True
        eos = st.req.eos_id
        return eos is not None and eos in st.gen

    def run(self, requests: Optional[List[Request]] = None, *,
            now_fn: Optional[Callable[[], float]] = None,
            poll: float = 0.001) -> List[RequestResult]:
        """Drain the queue (plus ``requests``, submitted first) through
        the slot pool; returns results in completion order.

        ``now_fn`` is the trace clock (seconds since trace start) gating
        admissions by ``Request.arrival``; without it every queued
        request is immediately admittable.  ``poll`` is the idle sleep
        while all slots are free and the next arrival is in the future.
        """
        for req in requests or ():
            self.submit(req)
        self._ensure_cache()
        now = now_fn or (lambda: float("inf"))
        t_run0 = time.perf_counter()
        # result/telemetry timestamps share the arrival clock when the
        # trace supplies one, so queue-wait / TTFT / latency subtract
        # consistent quantities; admission gating keeps the legacy
        # semantics (no now_fn -> every queued request is admittable)
        clock = now_fn or (lambda: time.perf_counter() - t_run0)
        done: List[RequestResult] = []

        while self._sched.has_work():
            # ---- admissions: fill every free slot with an arrived req
            while self._sched.queue and self._sched._free and \
                    self._requests[self._sched.queue[0]].arrival <= now():
                req = self._requests[self._sched.queue[0]]
                if self.paged:
                    # one admission in flight at a time: the next
                    # request's prefix match must see this prompt's
                    # pages, which only publish when its last chunk
                    # lands — identical prompts arriving together
                    # still share
                    if self._prefilling:
                        break
                    need = int(req.prompt.shape[0]) + req.max_tokens - 1
                    if not self.kv.can_admit(req.prompt, need) and \
                            not self.kv.try_reclaim(req.prompt, need):
                        break   # head-of-line waits for freed pages
                slot, rid = self._sched.admit()
                if self.paged:
                    self._admit_paged(slot, req, clock)
                    continue    # finishes (if ever) at promotion
                self._admit(slot, req, clock)
                if req.max_tokens <= 1:
                    self._sync_slot(slot, None, None)
                    done.append(self._finish(slot, clock()))

            # ---- chunked prefill: one chunk of the oldest admission,
            #      interleaved with the decode bursts below
            if self._prefilling:
                r = self._run_prefill_chunk(clock)
                if r is not None:
                    done.append(r)

            active = [s for s in self._sched.active_slots
                      if s in self._state]
            telemetry.gauge("serve.slots_active").set(len(active))
            if not active:
                if self._sched.queue and not self._prefilling:
                    time.sleep(poll)       # waiting on the next arrival
                continue

            # ---- decode burst: exact to the tightest max_tokens,
            #      EOS checked at the boundary
            k = min([EOS_CHECK_EVERY]
                    + [self._state[s].remaining for s in active])
            burst: List[jax.Array] = []
            with telemetry.span("serve.decode_burst", steps=max(k, 1),
                                active=len(active),
                                attn_plan=self._attn_plan_key()):
                t_burst0 = time.perf_counter()
                for _ in range(max(k, 1)):
                    logits, self._cache = self._step(
                        self.params, self._tok, self._cache)
                    samp = self._sample(logits, self._temps)
                    self._tok = samp[:, None]
                    burst.append(samp)
                jax.block_until_ready(self._tok)
            self.metrics["decode_time"] += time.perf_counter() - t_burst0
            self.metrics["decode_steps"] += len(burst)
            self.metrics["useful_slot_steps"] += len(burst) * len(active)
            telemetry.counter("serve.decode_steps").add(len(burst))
            self._stall_run = 0            # decode ran; stall over
            for j in range(len(burst)):    # KV billed at true positions
                self.metrics["modeled_kv_bytes"] += \
                    self.modeled_kv_bytes_per_step(
                        [self._state[s].pos + j for s in active])
            self.metrics["modeled_kv_bytes_dense_rows"] += \
                len(burst) * self._dense_rows_kv_bytes_per_step()
            for s in active:
                self._state[s].remaining -= len(burst)
                self._state[s].pos += len(burst)

            # ---- sync + completions
            host = np.asarray(jnp.stack(burst, axis=0))   # (k, n_slots)
            for s in active:
                self._sync_slot(s, host, s)
                if self._slot_done(s):
                    done.append(self._finish(s, clock()))
            telemetry.gauge("serve.slots_active").set(
                self._sched.n_active)

        return done

    # -------------------------------------------------- compat interface

    def generate(self, prompts: jax.Array, n_steps: int,
                 frames: Optional[jax.Array] = None,
                 seed: int = 0) -> GenerationResult:
        """Lockstep-compatible front end: prompts (b, s) int32, up to
        ``n_steps`` tokens each, returned as a dense (b, steps) array.
        Rows that finish early (EOS) are padded with ``eos_id`` —
        post-EOS samples never leak into the result.

        Each row admits through the per-request batch-1 slot prefill
        (b small dispatches instead of the old single (b, s) batched
        prefill) — the deliberate trade for a cache that requests can
        enter and leave independently; decode runs fully batched."""
        self._key = jax.random.PRNGKey(seed)
        prompts_np = np.asarray(prompts)
        frames_np = None if frames is None else np.asarray(frames)
        reqs = [Request(prompt=prompts_np[i], max_tokens=n_steps,
                        temperature=self.temperature, eos_id=self.eos_id,
                        frames=None if frames_np is None
                        else frames_np[i])
                for i in range(prompts_np.shape[0])]
        results = {r.rid: r for r in self.run(reqs)}
        ordered = [results[req.rid] for req in reqs]
        steps = max(r.n_tokens for r in ordered)
        fill = self.eos_id if self.eos_id is not None else 0
        out = np.full((len(ordered), steps), fill, np.int32)
        for i, r in enumerate(ordered):
            out[i, :r.n_tokens] = r.tokens
        return GenerationResult(tokens=out, steps=steps)

    # ------------------------------------------------------- cost model

    def _attn_layer_windows(self) -> List[tuple]:
        """(window, layer_count) per attn-family layer kind in the
        stack — the layers that stream KV cache every decode step."""
        cfg = self.cfg
        out = []
        for kind in cfg.layer_pattern:
            if kind in ("attn", "moe"):
                out.append((cfg.window, cfg.repeats))
            elif kind == "local":
                out.append((cfg.local_window, cfg.repeats))
        for kind in cfg.tail_pattern:
            if kind in ("attn", "moe"):
                out.append((cfg.window, 1))
            elif kind == "local":
                out.append((cfg.local_window, 1))
        return out

    def _attn_plan_key(self) -> Optional[str]:
        """The decode-mode attention plan this engine's steps resolve to
        (``spec key @ shape -> kernel``) — attached to decode-burst and
        per-request decode spans so Perfetto traces attribute the time
        to a specific plan.  ``None`` until the first decode traces."""
        from repro import ops as rops
        for pl in reversed(rops.attn_plans()):
            if pl.spec.mode in ("decode", "decode_paged"):
                return f"{pl.spec.key}@{pl.shape_key}->{pl.kernel}"
        return None

    def modeled_kv_bytes_per_step(self, positions) -> int:
        """Modeled KV-cache HBM bytes one batched decode step streams,
        billed at the given true per-row positions (window-clamped when
        dense; whole history pages when paged — the paged kernel masks
        windows in-VMEM, so windowed layers still move every page)."""
        cfg = self.cfg
        total = 0
        for window, count in self._attn_layer_windows():
            total += count * bandwidth.decode_kv_bytes(
                positions, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                dtype=cfg.dtype, window=window,
                page_size=self.page_size)
        return total

    def _dense_rows_kv_bytes_per_step(self) -> int:
        """What dense per-slot rows stream per step: every slot's full
        ``max_len`` allocation (window-clamped for ring layers),
        regardless of true positions — the overstatement the paged
        billing corrects."""
        cfg = self.cfg
        positions = [self.max_len - 1] * self.n_slots
        total = 0
        for window, count in self._attn_layer_windows():
            total += count * bandwidth.decode_kv_bytes(
                positions, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                dtype=cfg.dtype, window=window)
        return total

    def modeled_bytes_per_token(self, positions=None) -> int:
        """Modeled HBM traffic of ONE batched decode step (the whole
        slot pool shares it): the GEMM weight stream (every projection
        leaf through VMEM once, at storage width — the term the
        mixed-precision path halves) plus the KV-cache stream billed at
        true per-row positions (live slots by default; pages touched,
        not ``max_len`` rows)."""
        from repro import quant
        total = quant.gemm_weight_bytes(self.params)
        if positions is None:
            positions = [self._state[s].pos
                         for s in sorted(self._state)]
        if positions:
            total += self.modeled_kv_bytes_per_step(positions)
        return total
