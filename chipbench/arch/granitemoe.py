"""The granite MoE decoder (``model_type`` ``granitemoe``): how the
benchmark maps its configuration file onto the program, counts its FLOPs
and names its layers.

An architecture's module, ``chipbench/arch/<model_type>.py``, has four
members, found through ``harness.arch``:

- ``SCOPES``: the ``jax.named_scope`` names of the program's train step
  that the layer times are read under (``scopes.layer_ms``);
- ``model_config(cfg)``: the program's ``ModelConfig``, refusing what the
  program cannot run as the file states it;
- ``forward_flops_per_token(cfg, seq_len)``: model FLOPs of the forward
  pass per token (``flops.train_per_token`` takes three times it);
- ``expert_ffn_least_s(cfg, rows, peaks)``: the least time of the routed
  expert FFN's work on ``rows`` kept rows (``layers.readings``).
"""

from __future__ import annotations

import math

SCOPES = ("embed", "attention", "moe/router", "moe/dispatch",
          "moe/exchange", "moe/expert_ffn", "moe/combine", "unembed_ce",
          "optimizer")


def model_config(cfg: dict):
    """The program's ModelConfig for a granite-style configuration file."""
    from chipbench import program

    program.import_program()
    from repro.models.model import ModelConfig
    from repro.models.moe import MoEConfig

    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // H
    # The program implements these at one value only.
    fixed = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
             "logits_scaling": 1.0, "attention_multiplier": hd ** -0.5,
             "rms_norm_eps": 1e-6, "hidden_act": "silu",
             "attention_bias": False}
    for key, want in fixed.items():
        got = cfg[key]
        same = (got == want if isinstance(want, (str, bool))
                else math.isclose(got, want, rel_tol=1e-9))
        if not same:
            raise SystemExit(f"chipbench: the program runs {key}={want!r} "
                             f"only; the configuration states {got!r}")
    prog = cfg["program"]
    dep = cfg["deployment"]
    return ModelConfig(
        name=cfg["name"], family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=H,
        n_kv_heads=cfg["num_key_value_heads"], head_dim=hd,
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        act="swiglu", norm="rmsnorm", rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        moe=MoEConfig(n_experts=cfg["num_local_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_expert=cfg["intermediate_size"],
                      n_padding_experts=dep["padding_experts"]),
        vocab_pad=prog["vocab_pad"], dtype=cfg["dtype"],
        remat=prog["remat"], remat_policy=prog["remat_policy"],
        attn_block=prog["attn_block"])


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward matmul FLOPs per token. Counted: the attention projections,
    the attention scores and the weighted sum of values, the router, the
    SwiGLU FFN of the ``num_experts_per_tok`` routed experts (routed rows
    only: no capacity padding) and the unembedding. Not counted:
    recomputation, norms, softmax, rotary tables and the optimizer.
    Attention convention: causal half, a query at position i attends to
    i + 1 keys, which averages (S + 1) / 2 over a sequence of S."""
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


def expert_ffn_least_s(cfg: dict, rows: float, peaks: dict):
    """``(seconds, bound)``: the least time the chip needs for the routed
    SwiGLU expert FFN, forward and backward, on ``rows`` kept routed rows
    (summed over layers), and which bound (``compute`` or ``memory``) sets
    it.

    FLOPs: 2 x d x 2f (gate and up) + 2 x f x d (down) a row forward, three
    times that with the backward; no padding rows, no recompute. Bytes: the
    forward reads the expert weights and the rows in and writes the rows
    out; the backward reads the weights, writes their gradients, reads the
    rows in and their output gradients and writes the input gradients. So
    the weights move three times and the rows five, at the configuration's
    element size; intermediates are taken to stay on the core.
    """
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    width = {"bfloat16": 2, "float32": 4}[cfg["dtype"]]
    weights = (cfg["num_hidden_layers"] * cfg["num_local_experts"]
               * 3 * d * f * width)
    compute = 3 * (2 * d * 2 * f + 2 * f * d) * rows \
        / peaks["bf16_flops_per_s"]
    memory = (3 * weights + 5 * rows * d * width) / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
