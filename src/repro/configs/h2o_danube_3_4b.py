"""h2o-danube-3-4b — llama-style dense decoder [H2O-Danube3 Technical
Report, arXiv:2407.09276; hf:h2oai/h2o-danube3-4b-base].

24 layers, d_model 3840, 32 query and 8 KV heads of 120, SwiGLU d_ff
10240, vocab 32000.  The window of 4096 bounds the KV cache, so the
config counts as sub-quadratic.

``h2o-danube-3-4b-12l`` is one stage of a training deployment on 8 v5e
chips: two pipeline stages of 12 layers, each stage a 2x2 (data, model)
mesh.  Every width is the published one; only the depth is the stage's.
"""

import dataclasses

from repro.configs.base import ModelConfig, register

FULL = register(ModelConfig(
    name="h2o-danube-3-4b", family="dense",
    n_layers=24, d_model=3840, n_heads=32, n_kv_heads=8,
    d_ff=10240, vocab=32000, head_dim=120,
    window=4096,
    notes="SWA -> long_500k runs",
))

register(dataclasses.replace(FULL, name="h2o-danube-3-4b-12l",
                             n_layers=12))

register(ModelConfig(
    name="h2o-danube-3-4b-smoke", family="dense",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
    d_ff=128, vocab=512, head_dim=16, window=32,
    dtype="float32",
))
