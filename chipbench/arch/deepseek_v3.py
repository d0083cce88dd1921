"""The DeepSeek-V3 decoder (``model_type`` ``deepseek_v3``; Moonlight-16B-A3B
is one): how the benchmark maps its configuration file onto the program,
counts its FLOPs and names its layers, at one chip's share of an
expert-parallel group.

The members ``harness.arch`` looks for are those ``arch/granitemoe.py``
describes; this module adds ``mla_least_s``, the least time of the latent
attention's work, which its roofline reader takes.

The file's ``n_routed_experts`` counts the experts held here, from
``deployment.held_experts[0]`` on; the router routes over
``published.n_routed_experts``. The file's ``assumed`` gives the balance
loss weight and the bias speed, which the model's config does not state.
"""

from __future__ import annotations

# ``attention`` is the causal softmax attention within ``mla`` (the
# blockwise kernel the GQA models run); ``mla`` the rest of the layer.
SCOPES = ("embed", "mla", "attention", "dense_ffn", "moe/router",
          "moe/dispatch", "moe/exchange", "moe/expert_ffn",
          "moe/shared_expert", "moe/combine", "unembed_ce", "optimizer")

# Keys the program implements at one value only.
FIXED = {"hidden_act": "silu", "attention_bias": False, "q_lora_rank": None,
         "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
         "scoring_func": "sigmoid", "norm_topk_prob": True, "seq_aux": True,
         "tie_word_embeddings": False, "moe_layer_freq": 1,
         "num_nextn_predict_layers": 0}


def sizes(cfg: dict) -> dict:
    """The shapes the counts below use."""
    H = cfg["num_attention_heads"]
    L = cfg["num_hidden_layers"]
    n_dense = cfg["first_k_dense_replace"]
    return {"d": cfg["hidden_size"], "H": H, "r": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "L": L, "L_dense": n_dense,
            "L_moe": L - n_dense, "f_dense": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"],
            "E": cfg["published"]["n_routed_experts"],
            "E_held": cfg["n_routed_experts"],
            "k": cfg["num_experts_per_tok"],
            "shared": cfg["n_shared_experts"], "V": cfg["vocab_size"]}


def model_config(cfg: dict):
    """The program's ModelConfig for a DeepSeek-V3 configuration file at
    one chip's share; refuses what the program cannot run as stated."""
    from chipbench import program

    for key, want in FIXED.items():
        if cfg.get(key) != want:
            raise SystemExit(f"chipbench: the program runs {key}={want!r} "
                             f"only; the configuration states "
                             f"{cfg.get(key)!r}")
    if cfg.get("rope_scaling") is not None:
        raise SystemExit("chipbench: the program runs no rope_scaling")
    s = sizes(cfg)
    if cfg["num_key_value_heads"] != s["H"]:
        raise SystemExit("chipbench: latent attention has one key and value "
                         "head per query head")
    if not 0 <= s["L_dense"] < s["L"]:
        raise SystemExit(f"chipbench: first_k_dense_replace {s['L_dense']} "
                         f"leaves no MoE layer of {s['L']}")
    held = cfg["deployment"]["held_experts"]
    first = held[0]
    if (held[1] != s["E_held"] or s["E"] % s["E_held"]
            or first % s["E_held"] or not 0 <= first < s["E"]):
        raise SystemExit(f"chipbench: experts held {held} are not one share "
                         f"of {s['E']} in {s['E_held']}s")

    program.import_program()
    from repro.models.layers import MLAConfig
    from repro.models.model import ModelConfig
    from repro.models.moe import MoEConfig

    prog = cfg["program"]
    assumed = cfg["assumed"]
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=s["L"],
        n_dense_layers=s["L_dense"], d_model=s["d"], n_heads=s["H"],
        n_kv_heads=s["H"], d_ff=s["f_dense"], vocab=s["V"], act="swiglu",
        norm="rmsnorm", norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]), tie_embeddings=False,
        mla=MLAConfig(kv_lora_rank=s["r"], qk_nope_dim=s["nope"],
                      qk_rope_dim=s["rope"], v_head_dim=s["v"]),
        moe=MoEConfig(n_experts=s["E"], top_k=s["k"], d_expert=s["f"],
                      score="sigmoid",
                      routed_scale=float(cfg["routed_scaling_factor"]),
                      bias_speed=float(assumed["bias_update_speed"]),
                      balance_alpha=float(assumed["aux_loss_alpha"]),
                      n_shared=s["shared"], n_held=s["E_held"],
                      held_first=first),
        vocab_pad=prog["vocab_pad"], dtype=cfg["dtype"],
        remat=prog["remat"], remat_policy=prog["remat_policy"],
        attn_block=prog["attn_block"])


def _mla_flops(s: dict, seq_len: int) -> tuple[float, float]:
    """Forward FLOPs per token of one layer's latent attention:
    ``(projections, causal scores and weighted values)``."""
    H, qk = s["H"], s["nope"] + s["rope"]
    proj = (2 * s["d"] * H * qk + 2 * s["d"] * (s["r"] + s["rope"])
            + 2 * s["r"] * H * (s["nope"] + s["v"]) + 2 * H * s["v"] * s["d"])
    keys = (seq_len + 1) / 2
    return proj, 2 * keys * H * qk + 2 * keys * H * s["v"]


def _swiglu(d: int, f: int) -> int:
    return 2 * d * 2 * f + 2 * f * d


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward matmul FLOPs per token of this chip's share. Counted: the
    latent attention's projections, its causal scores and weighted values
    (a query at position i attends to i + 1 keys), the dense layer's
    SwiGLU, the router over all experts, the shared experts, the held
    routed experts on the rows routed to them (``k * E_held / E`` a token on
    average: no capacity padding) and the unembedding over the vocabulary
    slice. Not counted: recomputation, norms, softmax, rotary tables, the
    bias update and the optimizer."""
    s = sizes(cfg)
    proj, scores = _mla_flops(s, seq_len)
    dense = _swiglu(s["d"], s["f_dense"])
    router = 2 * s["d"] * s["E"]
    shared = _swiglu(s["d"], s["shared"] * s["f"])
    routed = s["k"] * s["E_held"] / s["E"] * _swiglu(s["d"], s["f"])
    return (s["L"] * (proj + scores) + s["L_dense"] * dense
            + s["L_moe"] * (router + shared + routed) + 2 * s["d"] * s["V"])


def _width(cfg: dict) -> int:
    return {"bfloat16": 2, "float32": 4}[cfg["dtype"]]


def expert_ffn_least_s(cfg: dict, rows: float, peaks: dict):
    """``(seconds, bound)``: the least time the chip needs for the held
    routed experts' SwiGLU FFN, forward and backward, on ``rows`` kept
    routed rows (summed over layers), and which bound sets it. FLOPs and
    bytes as ``arch/granitemoe.py`` counts them: the held experts' weights
    move three times, the rows five."""
    s = sizes(cfg)
    w = _width(cfg)
    weights = s["L_moe"] * s["E_held"] * 3 * s["d"] * s["f"] * w
    compute = 3 * _swiglu(s["d"], s["f"]) * rows / peaks["bf16_flops_per_s"]
    memory = (3 * weights + 5 * rows * s["d"] * w) / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def mla_least_s(cfg: dict, seq_len: int, tokens: int, peaks: dict):
    """``(seconds, bound)``: the least time the chip needs for one step's
    latent attention over ``tokens`` tokens in sequences of ``seq_len``, in
    every layer, and which bound sets it.

    FLOPs: the forward's projections and causal scores (``_mla_flops``)
    four times: forward, the full-remat recompute, and a backward of twice
    the forward. Bytes: the attention weights read in the forward, the
    recompute and the backward and their gradients written (four times);
    the layer's input read three times (forward, recompute, backward), its
    output written, its output gradient read and its input gradient
    written (six times the rows). Scores and probabilities are taken to
    stay on the core."""
    s = sizes(cfg)
    proj, scores = _mla_flops(s, seq_len)
    w = _width(cfg)
    H = s["H"]
    weights = (s["d"] * H * (s["nope"] + s["rope"])
               + s["d"] * (s["r"] + s["rope"]) + s["r"]
               + s["r"] * H * (s["nope"] + s["v"]) + H * s["v"] * s["d"]) * w
    compute = 4 * s["L"] * (proj + scores) * tokens \
        / peaks["bf16_flops_per_s"]
    memory = s["L"] * (4 * weights + 6 * tokens * s["d"] * w) \
        / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
