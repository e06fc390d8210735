"""Operations from shapes, and the chips' published peaks: the yardstick
for `mfu`."""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; an unknown kind is
    an error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table["chips"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; known: {sorted(table['chips'])}")
    return table["chips"][device_kind]


def matmul_params(conf: dict) -> int:
    """Weights that enter a matrix product once per token (the embedding
    lookup does not)."""
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    KV = conf.get("num_key_value_heads", H)
    hd = conf.get("head_dim", D // H)
    F, V = conf["ffn_dim"], conf["vocab_size"]
    per_layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    return conf["num_hidden_layers"] * per_layer + D * V


def train_flops(conf: dict, batch: int, seq: int) -> int:
    """Operations one training step needs (forward and backward, no
    recomputation): 6 per weight per token for the matrix products, and
    for causal attention the score and value products over the S(S+1)/2
    query-key pairs each sequence has, 2 operations each per head
    dimension, times 3 for the backward pass."""
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    hd = conf.get("head_dim", D // H)
    L = conf["num_hidden_layers"]
    dense = 6 * matmul_params(conf) * batch * seq
    pairs = seq * (seq + 1) // 2
    attn = 3 * 2 * 2 * batch * L * H * hd * pairs
    return dense + attn
