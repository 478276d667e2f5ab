"""Open-loop request traces for serving cells, driven by a traffic file.

Arrivals are fixed in advance and never wait for the server.  Two
arrival kinds:

* ``requests``: independent Poisson arrivals at ``rate`` per second;
* ``sessions``: Poisson session starts at ``rate`` per second.  A
  session picks one of ``system_prompts`` shared system prompts
  (Zipf-skewed), then runs 1 + geometric turns, each arriving an
  exponential think time after the one before.  A turn's prompt is the
  previous prompt, a synthetic reply as long as the previous turn's
  ``max_tokens`` (rounded up), and a new user message.

Every length is rounded up to ``round_to`` tokens.  The sizes and
arrival times are drawn from the file's ``shape_seed``, so every run
seed gets the same set of work; the run seed only reorders the sizes
over the arrival times and draws the token ids.  A request's length
limits (``max_prompt``, ``max_len``) hold by construction.
"""

from __future__ import annotations

import hashlib
from typing import Dict, List

import numpy as np


def _round_up(x, q: int):
    return -(-np.asarray(x) // q) * q


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _arrivals(rng, rate: float, seconds: float) -> np.ndarray:
    n = int(rate * seconds * 2 + 50)
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    while t[-1] < seconds:                      # pragma: no cover
        t = np.concatenate([t, t[-1] + np.cumsum(
            rng.exponential(1.0 / rate, n))])
    return t[t < seconds] - 0.0


def _session_shapes(p: dict, rng, n: int) -> List[dict]:
    """Sizes of ``n`` sessions: system prompt, per-turn message and
    output lengths, think times; turns stop where a limit would break."""
    q = p["round_to"]
    k = p["system_prompts"]
    zipf = 1.0 / np.arange(1, k + 1) ** p["zipf_s"]
    out = []
    for _ in range(n):
        turns = int(min(rng.geometric(1.0 / p["mean_turns"]),
                        p["max_turns"]))
        msgs = _round_up(_lognormal(rng, p["message"], turns), q)
        outs = _lognormal(rng, p["max_tokens"], turns)
        think = rng.exponential(p["think_s"], turns)
        sys_i = int(rng.choice(k, p=zipf / zipf.sum()))
        plen, keep = p["system_len"], []
        for t in range(turns):
            if t:
                plen += int(_round_up(outs[t - 1], q))
            plen += int(msgs[t])
            if plen > p["max_prompt"] or plen + outs[t] > p["max_len"]:
                break
            keep.append(t)
        if keep:
            out.append({"system": sys_i, "msgs": msgs[:len(keep)],
                        "outs": outs[:len(keep)],
                        "think": think[:len(keep)]})
    return out


def generate(p: dict, seed: int, seconds: float, vocab: int,
             rate: float = None) -> List[Dict]:
    """The requests arriving in ``[0, seconds)``, sorted by arrival:
    dicts of ``arrival`` (s), ``prompt`` (int32 ids), ``max_tokens``,
    ``session`` and ``turn``."""
    rate = p["rate"] if rate is None else rate
    shape = np.random.default_rng(p["shape_seed"])
    rng = np.random.default_rng(seed)
    starts = _arrivals(shape, rate, seconds)
    reqs: List[Dict] = []
    if p["arrivals"] == "requests":
        q = p["round_to"]
        plens = _round_up(_lognormal(shape, p["prompt"], len(starts)), q)
        outs = _lognormal(shape, p["max_tokens"], len(starts))
        order = rng.permutation(len(starts))
        for i, t in enumerate(starts):
            j = order[i]
            reqs.append({"arrival": float(t), "max_tokens": int(outs[j]),
                         "prompt": rng.integers(0, vocab, int(plens[j]))
                         .astype(np.int32), "session": i, "turn": 0})
        return reqs
    shapes = _session_shapes(p, shape, len(starts))
    starts = starts[:len(shapes)]
    order = rng.permutation(len(shapes))
    systems = rng.integers(0, vocab, (p["system_prompts"], p["system_len"]))
    for i, t0 in enumerate(starts):
        s = shapes[order[i]]
        prompt = systems[s["system"]]
        t = t0
        for turn in range(len(s["msgs"])):
            if turn:
                t += s["think"][turn]
                reply = rng.integers(0, vocab, int(_round_up(
                    s["outs"][turn - 1], p["round_to"])))
                prompt = np.concatenate([prompt, reply])
            prompt = np.concatenate(
                [prompt, rng.integers(0, vocab, int(s["msgs"][turn]))])
            if t >= seconds:
                break
            reqs.append({"arrival": float(t),
                         "prompt": prompt.astype(np.int32),
                         "max_tokens": int(s["outs"][turn]),
                         "session": i, "turn": turn})
    reqs.sort(key=lambda r: r["arrival"])
    return reqs


def warmup(p: dict, vocab: int, seed: int) -> List[Dict]:
    """Requests that drive every program a window of this mix uses: one
    prompt of ``max_prompt`` tokens (a chunk at every start), then
    prompts of one chunk whose outputs end decode bursts of every
    length up to ``burst``."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    reqs = [{"prompt": rng.integers(0, vocab, p["max_prompt"]),
             "max_tokens": p["burst"] + 1}]
    for m in range(2, p["burst"] + 1):
        reqs.append({"prompt": rng.integers(0, vocab, p["round_to"]),
                     "max_tokens": m})
    for r in reqs:
        r["prompt"] = r["prompt"].astype(np.int32)
        r["arrival"] = 0.0
    return reqs


def _page_key(tokens: np.ndarray, n: int) -> bytes:
    return hashlib.blake2b(tokens[:n].tobytes(), digest_size=16).digest()


def prefix_plan(reqs: List[Dict], page: int) -> List[int]:
    """Prompt tokens each request could take from a prefix cache that
    never evicts, admitting in arrival order: whole pages of the longest
    cached prefix, and never the last prompt token."""
    cached = set()
    shared = []
    for r in reqs:
        toks = np.ascontiguousarray(r["prompt"], np.int32)
        plen, covered = len(toks), 0
        for i in range(-(-plen // page)):
            n = min((i + 1) * page, plen)
            if _page_key(toks, n) not in cached:
                break
            covered = n
        shared.append(min(covered, plen - 1))
        for i in range(-(-plen // page)):
            cached.add(_page_key(toks, min((i + 1) * page, plen)))
    return shared


def expected_prefix_share(reqs: List[Dict], page: int) -> float:
    """Share of prompt tokens a never-evicting prefix cache serves."""
    shared = prefix_plan(reqs, page)
    total = sum(len(r["prompt"]) for r in reqs)
    return sum(shared) / total if total else 0.0
