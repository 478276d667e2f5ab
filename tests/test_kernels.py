"""Per-kernel correctness sweeps: Pallas (interpret=True) vs pure-jnp
oracles, across shapes / dtypes / strategies, plus gradient checks for the
custom-VJP gemm wrapper."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tiling import TileConfig
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gemm_aie import gemm_aie
from repro.kernels.gemm_tb import gemm_tb

# These suites exercise the deprecated legacy entrypoints on purpose
# (old-vs-new parity is the point); the -W error::DeprecationWarning
# CI invocation must not fail them.
pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")



def _rand(key, shape, dtype):
    if dtype == jnp.int8:
        return jax.random.randint(key, shape, -127, 128, jnp.int32) \
            .astype(jnp.int8)
    return jax.random.normal(key, shape, dtype)


GEMM_SHAPES = [
    (256, 256, 256),
    (384, 512, 640),        # multi-block every dim
    (128, 1024, 256),
    (8, 256, 128),          # skinny decode-like M
]
DTYPES = [jnp.float32, jnp.bfloat16, jnp.int8]
TILES = [TileConfig(128, 128, 128, "aie"), TileConfig(128, 256, 128, "aie"),
         TileConfig(128, 128, 128, "tb"), TileConfig(128, 256, 256, "tb")]


@pytest.mark.parametrize("shape", GEMM_SHAPES, ids=str)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("tile", TILES,
                         ids=lambda t: f"{t.strategy}-{t.bm}x{t.bk}x{t.bn}")
def test_gemm_kernels_match_oracle(shape, dtype, tile):
    m, k, n = shape
    if m < tile.bm and tile.bm > 128:
        pytest.skip("tile larger than problem")
    key = jax.random.PRNGKey(0)
    a = _rand(key, (m, k), dtype)
    b = _rand(jax.random.PRNGKey(1), (k, n), dtype)

    # pad to tile multiples, run the kernel, slice back (what ops.py does)
    mp = -(-m // tile.bm) * tile.bm
    kp = -(-k // tile.bk) * tile.bk
    np_ = -(-n // tile.bn) * tile.bn
    ap = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    bp = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    fn = gemm_aie if tile.strategy == "aie" else gemm_tb
    got = fn(ap, bp, tile=tile, interpret=True)[:m, :n]

    want = ref.gemm_ref(a, b)
    assert got.dtype == want.dtype
    if dtype == jnp.int8:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        rtol = 1e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=rtol, atol=1e-3)


def test_ops_gemm_interpret_matches_ref(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    a = jax.random.normal(jax.random.PRNGKey(0), (4, 96, 192), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (192, 320), jnp.bfloat16)
    got = ops.gemm(a, w)
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    want = ops.gemm(a, w)
    assert got.shape == (4, 96, 320)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=1e-2)


def test_ops_gemm_grad_matches_jnp():
    a = jax.random.normal(jax.random.PRNGKey(0), (32, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 48), jnp.float32)

    def loss_ops(a, w):
        return jnp.sum(ops.gemm(a, w) ** 2)

    def loss_jnp(a, w):
        return jnp.sum((a @ w) ** 2)

    ga, gw = jax.grad(loss_ops, (0, 1))(a, w)
    ga2, gw2 = jax.grad(loss_jnp, (0, 1))(a, w)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(ga2), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw2), rtol=1e-4)


def test_gemm_int8_quantized_path():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 96), jnp.float32)
    xq, xs = ops.quantize_int8(x, axis=-1)          # (64,1) scales
    wq, ws = ops.quantize_int8(w, axis=0)           # (1,96) scales
    got = ops.gemm_int8(xq, wq, xs, ws)
    want = x @ w
    # int8 W8A8 quantization error ~1% relative on random gaussians
    err = np.linalg.norm(np.asarray(got - want)) / np.linalg.norm(
        np.asarray(want))
    assert err < 0.03


ATTN_CASES = [
    # (b, sq, skv, hq, hkv, d, causal, window[, q_offset])
    (1, 256, 256, 4, 4, 64, True, 0),
    (2, 256, 256, 8, 2, 64, True, 0),          # GQA
    (1, 128, 384, 4, 2, 64, True, 0),          # cross-block kv, q_offset
    (1, 256, 256, 4, 1, 96, True, 128),        # SWA + non-128 head dim
    (1, 192, 192, 2, 2, 64, False, 0),         # non-causal (encoder)
    (1, 256, 256, 4, 4, 128, True, 64),        # tight window
    # the forward's block skip: 10 of 16 blocks live
    (1, 512, 512, 2, 1, 64, True, 0),
    (1, 256, 512, 4, 2, 64, True, 0),          # q_offset 256, 2 q blocks
    (1, 328, 328, 2, 2, 64, True, 0),          # ragged sq
    (1, 512, 512, 2, 2, 64, True, 255),        # dead blocks before lo too
    (1, 256, 384, 2, 2, 64, True, 0, 126),     # q_offset off the blocks
    # q_offset 0 with sq > skv: rows from 319 see no key, and the last
    # q block's range is empty
    (1, 512, 256, 2, 2, 64, True, 64, 0),
]


def _ref_lse(q, k, *, causal, window, q_offset):
    """(b, hq, sq) log-sum-exp of the scores the mask leaves, +inf for a
    row it leaves none (the kernel's convention), and the (sq,) rows
    that see a key."""
    sq, skv, d = q.shape[1], k.shape[1], q.shape[3]
    k = jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q * d ** -0.5, k,
                   precision="highest")
    mask = ref._window_mask(sq, skv, causal=causal, window=window,
                            q_offset=skv - sq if q_offset is None
                            else q_offset)
    seen = mask.any(axis=1)
    lse = jax.nn.logsumexp(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.where(seen, lse, jnp.inf), seen


def _all_blocks(monkeypatch):
    """Make the forward compute every block under its mask: what the
    block skip has to equal bit for bit."""
    from repro.kernels import flash_attention as fa
    monkeypatch.setattr(fa, "kv_block_range",
                        lambda qi, *, nkv, **kw: (0, nkv - 1))


@pytest.mark.parametrize("case", ATTN_CASES, ids=str)
def test_flash_attention_matches_ref(case, monkeypatch):
    """Output and lse against the reference at 128-blocks, where the
    causal and window block skips engage; and bit for bit against the
    same kernel made to compute every block."""
    b, sq, skv, hq, hkv, d, causal, window, *rest = case
    q_offset = rest[0] if rest else None
    key = jax.random.PRNGKey(0)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, hq, d), jnp.float32)
    k = jax.random.normal(kk, (b, skv, hkv, d), jnp.float32)
    v = jax.random.normal(kv_, (b, skv, hkv, d), jnp.float32)
    kw = dict(causal=causal, window=window, q_offset=q_offset)
    got = flash_attention(q, k, v, bq=128, bkv=128, interpret=True, **kw)
    got_l, lse = flash_attention(q, k, v, bq=128, bkv=128, interpret=True,
                                 return_lse=True, **kw)
    want = ref.attention_ref(q, k, v, **kw)
    want_lse, seen = _ref_lse(q, k, **kw)
    seen_rows = np.asarray(seen)[None, :, None, None]
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(got))
    # a row that sees no key gives 0 (the reference averages every value)
    np.testing.assert_allclose(np.asarray(got),
                               np.where(seen_rows, np.asarray(want), 0.0),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=2e-3, atol=2e-3)
    _all_blocks(monkeypatch)
    every_o, every_lse = flash_attention.__wrapped__(
        q, k, v, bq=128, bkv=128, interpret=True, return_lse=True, **kw)
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(every_o))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(every_lse))


def test_flash_forward_dead_steps_fetch_no_new_block(monkeypatch):
    """The forward's kv index map sends a step outside its q block's live
    range to the live neighbour's block, so the pipeline issues no copy
    for it."""
    from repro.kernels import flash_attention as fa
    specs = []
    call = fa.pl.pallas_call
    monkeypatch.setattr(fa.pl, "pallas_call", lambda *a, **kw: specs.append(
        kw["in_specs"]) or call(*a, **kw))
    q = jnp.zeros((1, 512, 2, 64), jnp.float32)
    kv = jnp.zeros((1, 512, 1, 64), jnp.float32)
    jax.eval_shape(lambda q, kv: fa.flash_attention.__wrapped__(
        q, kv, kv, window=200, bq=128, bkv=128, interpret=True), q, kv)
    kv_map = specs[0][1].index_map
    got = {(qi, kvi): int(kv_map(1, qi, kvi)[2])
           for qi in range(4) for kvi in range(4)}
    # causal: q block 1 sees kv blocks 0-1, so steps 2 and 3 stay on 1;
    # window 200: q block 3 sees kv blocks 1-3, so step 0 reads block 1
    assert [got[1, kvi] for kvi in range(4)] == [0, 1, 1, 1]
    assert [got[3, kvi] for kvi in range(4)] == [1, 1, 2, 3]
    assert [got[0, kvi] for kvi in range(4)] == [0, 0, 0, 0]
    assert kv_map(1, 3, 0)[:2] == (0, 0)       # q head 1 reads kv head 0


def test_flash_attention_bf16():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 256, 4, 64),
                          jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 2, 64),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 2, 64),
                          jnp.bfloat16)
    got = flash_attention(q, k, v, bq=128, bkv=128, interpret=True)
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_attention_ref_window_equals_full_when_window_ge_seq():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 2, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 2, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 2, 32))
    full = ref.attention_ref(q, k, v, causal=True, window=0)
    wide = ref.attention_ref(q, k, v, causal=True, window=64)
    np.testing.assert_allclose(np.asarray(full), np.asarray(wide),
                               rtol=1e-6)
