"""Model FLOPs of a training step, computed from the configuration file.

Forward matmul FLOPs per token, as the module of the configuration's
``model_type`` counts them (``harness.arch``), times 3 for forward plus
backward.
"""

from __future__ import annotations

from chipbench import harness


def forward_per_token(cfg: dict, seq_len: int) -> float:
    return harness.arch(cfg).forward_flops_per_token(cfg, seq_len)


def train_per_token(cfg: dict, seq_len: int) -> float:
    return 3 * forward_per_token(cfg, seq_len)
