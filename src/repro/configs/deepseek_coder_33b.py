"""deepseek-coder-33b [dense] — llama-arch, as published (base model).

62L d_model=7168 56H (GQA kv=8, head 128) d_ff=19200 vocab=32256,
untied head, rope_theta 100000 with linear RoPE scaling x4 (16384
positions); 33.3B parameters, 3.64B at 6 layers
[arXiv:2401.14196; hf:deepseek-ai/deepseek-coder-33b-base config.json]
"""
from repro.configs.base import ArchCfg

CONFIG = ArchCfg(
    name="deepseek-coder-33b",
    family="dense",
    block="dense",
    n_layers=62,
    d_model=7168,
    n_heads=56,
    n_kv_heads=8,
    d_ff=19200,
    vocab=32256,
    rope_theta=100000.0,
    rope_scale=4.0,
    tie_embeddings=False,
)
