"""Operations and least bytes of a model's work, from its shapes alone.

The yardstick of the utilization and roofline metrics: every count here
comes from the configuration file's published sizes, never from the
program.  Multiply-adds count as two operations.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Iterator, NamedTuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown device raises."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} has no row in "
                       f"{PEAKS_FILE.name}: known {sorted(table)}")
    return table[device_kind]


def matmul_params(c: dict) -> int:
    """Weights that take part in a matrix product per token: the
    attention and MLP projections of every layer plus the lm head (the
    embedding is a gather, not a product)."""
    d, hd = c["hidden_size"], c["head_dim"]
    attn = d * hd * (2 * c["num_attention_heads"]
                     + 2 * c["num_key_value_heads"])
    mlp = 3 * d * c["intermediate_size"]
    return c["num_hidden_layers"] * (attn + mlp) + d * c["vocab_size"]


def _context(c: dict, position: int) -> int:
    """Keys one query at ``position`` attends to (causal, windowed)."""
    ctx = position + 1
    window = c.get("sliding_window") or 0
    return min(ctx, window) if window else ctx


def attention_flops(c: dict, context: float) -> float:
    """Forward operations of QK^T and PV for one query over ``context``
    keys, summed over the layers."""
    return (4.0 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * context)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward plus backward operations per trained token at
    ``seq_len``, causal, with recomputation not counted."""
    mean_ctx = sum(_context(c, p) for p in range(seq_len)) / seq_len
    return 6.0 * matmul_params(c) + 3.0 * attention_flops(c, mean_ctx)


def infer_flops(c: dict, position: int) -> float:
    """Forward operations for one token at ``position`` of its sequence."""
    return 2.0 * matmul_params(c) + attention_flops(c, _context(c, position))


def infer_flops_span(c: dict, start: int, stop: int) -> float:
    """Forward operations of the tokens at positions ``[start, stop)``."""
    n = stop - start
    if n <= 0:
        return 0.0
    mat = 2.0 * matmul_params(c) * n
    window = c.get("sliding_window") or 0
    if not window or stop <= window:
        ctx = (start + 1 + stop) * n / 2.0      # sum of p + 1 over span
    else:
        ctx = sum(_context(c, p) for p in range(start, stop))
    return mat + attention_flops(c, ctx)


class Gemm(NamedTuple):
    """One matrix product of a step: (m, k) x (k, n), ``count`` times,
    with operand and result widths in bytes."""
    name: str
    m: int
    k: int
    n: int
    count: int
    in_bytes: int = 2
    out_bytes: int = 2

    @property
    def flops(self) -> float:
        return 2.0 * self.m * self.k * self.n * self.count

    @property
    def min_bytes(self) -> float:
        """Each operand read once and the result written once."""
        return self.count * (self.in_bytes * (self.m * self.k
                                              + self.k * self.n)
                             + self.out_bytes * self.m * self.n)


def train_gemms(c: dict, tokens: int, passes: int = 3) -> Iterator[Gemm]:
    """The projection, MLP and lm-head products one train step needs
    over ``tokens`` rows: each ``passes`` times, the forward and the two
    backward products (input and weight gradient), which have the same
    shape up to transposition.  Recomputation is not counted."""
    d, hd, L = c["hidden_size"], c["head_dim"], c["num_hidden_layers"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    f, v = c["intermediate_size"], c["vocab_size"]
    n = L * passes
    yield Gemm("wq", tokens, d, hq * hd, n)
    yield Gemm("wk", tokens, d, hkv * hd, n)
    yield Gemm("wv", tokens, d, hkv * hd, n)
    yield Gemm("wo", tokens, hq * hd, d, n)
    yield Gemm("w_gate", tokens, d, f, n)
    yield Gemm("w_up", tokens, d, f, n)
    yield Gemm("w_down", tokens, f, d, n)
    yield Gemm("lm_head", tokens, d, v, passes, out_bytes=4)


def roofline_seconds(gemms, peak: dict) -> float:
    """Least time of the products on a chip: each bounded by its
    operations at the bf16 peak or its bytes at the HBM peak."""
    return sum(max(g.flops / peak["bf16_flops"],
                   g.min_bytes / peak["hbm_bytes_per_s"]) for g in gemms)
