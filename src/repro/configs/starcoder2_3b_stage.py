"""StarCoder2-3B, one stage of a three-stage pipeline — 10 of its 30 layers.

Every width as published (d_model=3072 24H, GQA kv=2, d_ff=12288,
vocab=49152, sliding window 4096). [arXiv:2402.19173]  The stage holds both
the embedding and the head, so that it trains as a whole model on one chip:
about 0.15e9 parameters more than the first or the last stage of a real
pipeline, which holds one of the two.
"""

from repro.configs import starcoder2_3b
from repro.configs.base import ModelConfig

CONFIG = starcoder2_3b.CONFIG.with_(name="starcoder2-3b-stage", n_layers=10)


def reduced() -> ModelConfig:
    # window 16 under the tiny sequences (32, 64): the band, not the
    # causal triangle, decides which keys a query sees
    return starcoder2_3b.reduced().with_(name="starcoder2-3b-stage",
                                         n_layers=2, sliding_window=16)
