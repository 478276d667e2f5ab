"""The traffic generators: seeded, rounded, sessions that extend, and a
bounded set of prefill programs."""

import json
from pathlib import Path

import numpy as np
import pytest

import smoke  # noqa: F401
from harness import load_module

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
gen = load_module(CHIP / "traffic" / "open_loop.py")
batches = load_module(CHIP / "traffic" / "train_batch.py")
BIG_SEED = 2**31 + 12345


def prefill_starts(reqs, page, chunk):
    """Every chunk start the trace's prefills use."""
    starts = set()
    for r, s in zip(reqs, gen.prefix_plan(reqs, page)):
        starts.update(range(s, len(r["prompt"]), chunk))
    return starts


#: independent requests (no cell uses this arrival kind yet; PERF.md
#: keeps it for the short-request cell of a later PR)
SHORT = {"arrivals": "requests", "rate": 4.0, "shape_seed": 20261018,
         "prompt": {"median": 256, "sigma": 0.5, "min": 128, "max": 512},
         "max_tokens": {"median": 48, "sigma": 0.6, "min": 16, "max": 128},
         "round_to": 128, "max_prompt": 512, "max_len": 640,
         "page_size": 512, "prefill_chunk": 128, "burst": 8}


def traffic(name):
    """The train mix, or a serving mix at full size (no serving cell is
    in the benchmark yet: the chat mix is kept with the tests)."""
    if name == "short-unshared":
        return SHORT
    if name == "chat-sessions":
        return json.loads((HERE / "data" / f"{name}.json").read_text())
    return json.loads((CHIP / "traffic" / f"{name}.json").read_text())


def same(a, b):
    return len(a) == len(b) and all(
        x["arrival"] == y["arrival"] and x["max_tokens"] == y["max_tokens"]
        and np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))


@pytest.mark.parametrize("mix,vocab", [("chat-sessions", 49152),
                                       ("short-unshared", 32000)])
def test_seeded(mix, vocab):
    p = traffic(mix)
    a = gen.generate(p, BIG_SEED, 30, vocab)
    assert same(a, gen.generate(p, BIG_SEED, 30, vocab))
    assert not same(a, gen.generate(p, 7, 30, vocab))


@pytest.mark.parametrize("mix,vocab", [("chat-sessions", 49152),
                                       ("short-unshared", 32000)])
def test_lengths_and_limits(mix, vocab):
    p = traffic(mix)
    reqs = gen.generate(p, 3, 30, vocab)
    assert reqs
    for r in reqs:
        assert len(r["prompt"]) % p["round_to"] == 0
        assert len(r["prompt"]) <= p["max_prompt"]
        assert len(r["prompt"]) + r["max_tokens"] <= p["max_len"]
        assert 0 <= r["arrival"] < 30
        assert r["prompt"].dtype == np.int32
        assert r["prompt"].max() < vocab
    assert [r["arrival"] for r in reqs] == sorted(r["arrival"] for r in reqs)


def test_seeds_share_the_work():
    """Every seed draws the same set of sizes for the short mix."""
    p = traffic("short-unshared")
    a, b = (gen.generate(p, s, 30, 32000) for s in (1, BIG_SEED))
    key = lambda rs: sorted((len(r["prompt"]), r["max_tokens"]) for r in rs)
    assert key(a) == key(b)
    assert [r["arrival"] for r in a] == [r["arrival"] for r in b]


def test_sessions_extend_their_previous_turn():
    p = traffic("chat-sessions")
    reqs = gen.generate(p, 11, 30, 49152)
    by_session = {}
    for r in reqs:
        by_session.setdefault(r["session"], []).append(r)
    multi = [s for s in by_session.values() if len(s) > 1]
    assert multi
    for turns in by_session.values():
        assert [t["turn"] for t in turns] == list(range(len(turns)))
        for prev, cur in zip(turns, turns[1:]):
            n = len(prev["prompt"])
            assert np.array_equal(cur["prompt"][:n], prev["prompt"])
            reply = -(-prev["max_tokens"] // p["round_to"]) * p["round_to"]
            assert len(cur["prompt"]) > n + reply
            assert cur["arrival"] > prev["arrival"]
    systems = {tuple(t[0]["prompt"][:p["system_len"]])
               for t in by_session.values()}
    assert 1 < len(systems) <= p["system_prompts"]


def test_expected_prefix_share():
    p = traffic("chat-sessions")
    reqs = gen.generate(p, 11, 30, 49152)
    share = gen.expected_prefix_share(reqs, p["page_size"])
    # by hand from the plan: whole pages of the longest cached prefix
    plan = gen.prefix_plan(reqs, p["page_size"])
    assert all(s % p["page_size"] == 0 for s in plan)
    assert share == pytest.approx(
        sum(plan) / sum(len(r["prompt"]) for r in reqs))
    assert 0.3 < share < 0.7
    short = gen.generate(traffic("short-unshared"), 11, 30, 32000)
    assert gen.expected_prefix_share(short, 512) == 0.0


@pytest.mark.parametrize("mix,vocab", [("chat-sessions", 49152),
                                       ("short-unshared", 32000)])
def test_prefill_starts_are_warmed(mix, vocab):
    """At most 16 chunk starts, and the warm-up drives every one."""
    p = traffic(mix)
    starts = set()
    for seed in (1, 2, BIG_SEED):
        starts |= prefill_starts(gen.generate(p, seed, 30, vocab),
                                     p["page_size"], p["prefill_chunk"])
    warm = prefill_starts(gen.warmup(p, vocab, 0), p["page_size"],
                              p["prefill_chunk"])
    assert len(warm) <= 16
    assert starts <= warm
    bursts = {min(r["max_tokens"] - 1, p["burst"])
              for r in gen.warmup(p, vocab, 0)}
    assert bursts == set(range(1, p["burst"] + 1))


def test_train_batches():
    p = traffic("train-2k")
    a = batches.batch(p, BIG_SEED, 0, 49152)
    assert a["tokens"].shape == (p["global_batch"], p["seq_len"])
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert np.array_equal(a["tokens"],
                          batches.batch(p, BIG_SEED, 0, 49152)["tokens"])
    b = batches.batch(p, BIG_SEED, 1, 49152)
    rows = {r.tobytes() for r in np.concatenate([a["tokens"], b["tokens"]])}
    assert len(rows) == 2 * p["global_batch"]
