"""Model FLOPs of a training step, computed from the configuration file.

Forward matmul FLOPs per token, times 3 for forward plus backward.
Counted: the attention projections, the attention scores and the weighted
sum of values, the router, the SwiGLU FFN of the ``num_experts_per_tok``
routed experts (routed rows only: no capacity padding) and the
unembedding. Not counted: recomputation, norms, softmax, rotary tables and
the optimizer. Attention convention: causal half, a query at position i
attends to i + 1 keys, which averages (S + 1) / 2 over a sequence of S.
"""

from __future__ import annotations


def forward_per_token(cfg: dict, seq_len: int) -> float:
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    K = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    f = cfg["intermediate_size"]
    E = cfg["num_local_experts"]
    k = cfg["num_experts_per_tok"]
    V = cfg["vocab_size"]
    L = cfg["num_hidden_layers"]
    proj = 2 * d * (H * hd + 2 * K * hd) + 2 * H * hd * d
    keys = (seq_len + 1) / 2
    scores = 2 * 2 * keys * H * hd            # q.k and p.v
    router = 2 * d * E
    experts = k * (2 * d * 2 * f + 2 * f * d)
    unembed = 2 * d * V
    return L * (proj + scores + router + experts) + unembed


def train_per_token(cfg: dict, seq_len: int) -> float:
    return 3 * forward_per_token(cfg, seq_len)
