"""Operation counts against hand counts, and the peak table."""

import json
from pathlib import Path

import pytest

import smoke  # noqa: F401  (puts the benchmark on sys.path)
import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


#: H2O-Danube3-4B's published sizes (no cell runs it yet)
DANUBE = {"num_hidden_layers": 24, "hidden_size": 3840,
          "num_attention_heads": 32, "num_key_value_heads": 8,
          "head_dim": 120, "intermediate_size": 10240, "vocab_size": 32000,
          "sliding_window": 4096}


def config(name):
    if name == "h2o-danube-3-4b":
        return DANUBE
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_smollm_matmul_params():
    # per layer: 960*64*(2*15 + 2*5) + 3*960*2560 = 9,830,400; lm head
    # 960*49152 = 47,185,920
    assert flops.matmul_params(config("smollm-360m")) == \
        32 * 9_830_400 + 47_185_920 == 361_758_720


def test_danube_matmul_params():
    # per layer: 3840*120*(2*32 + 2*8) + 3*3840*10240 = 154,828,800;
    # lm head 3840*32000 = 122,880,000
    assert flops.matmul_params(config("h2o-danube-3-4b")) == \
        24 * 154_828_800 + 122_880_000 == 3_838_771_200


def test_train_flops_per_token_smollm():
    # 6 * 361,758,720 + 3 * 4 * 32 layers * 15 heads * 64 * 1024.5
    # (mean causal context at seq 2048)
    want = 6 * 361_758_720 + 12 * 32 * 15 * 64 * 1024.5
    assert flops.train_flops_per_token(config("smollm-360m"), 2048) \
        == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(2.548224e9, rel=1e-6)


def test_train_flops_per_token_danube_windowed():
    # seq 8192 over a 4096 window: positions >= 4096 see 4096 keys
    c = config("h2o-danube-3-4b")
    ctx = (sum(range(1, 4097)) + 4096 * 4096) / 8192
    want = 6 * 3_838_771_200 + 12 * 24 * 32 * 120 * ctx
    assert flops.train_flops_per_token(c, 8192) == pytest.approx(want,
                                                                 rel=1e-12)


def test_inference_counts():
    c = config("smollm-360m")
    # one token at position 99 attends to 100 keys
    assert flops.infer_flops(c, 99) == 2 * 361_758_720 \
        + 4 * 32 * 15 * 64 * 100
    # a span is the sum of its tokens
    assert flops.infer_flops_span(c, 10, 20) == pytest.approx(
        sum(flops.infer_flops(c, p) for p in range(10, 20)), rel=1e-12)
    d = config("h2o-danube-3-4b")
    assert flops.infer_flops_span(d, 4000, 4200) == pytest.approx(
        sum(flops.infer_flops(d, p) for p in range(4000, 4200)), rel=1e-12)
    assert flops.infer_flops_span(c, 5, 5) == 0.0


def test_train_gemms_roofline():
    c = config("smollm-360m")
    gs = list(flops.train_gemms(c, 16384))
    # forward + two backward products: 3 * 2 * tokens * matmul params
    assert sum(g.flops for g in gs) == 3 * 2 * 16384 * 361_758_720
    peak = flops.peaks("TPU v5 lite")
    # compute-bound at 16k rows: the least time is flops / bf16 peak
    assert flops.roofline_seconds(gs, peak) == pytest.approx(
        sum(g.flops for g in gs) / 197e12, rel=1e-3)


def test_peaks():
    p = flops.peaks("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"],
            p["hbm_bytes"]) == (197e12, 393e12, 819e9, 16e9)
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
