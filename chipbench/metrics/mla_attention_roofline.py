"""The latent attention's share of its roofline: the least time the chip
needs for a step's MLA work (``mla_least_s`` of the architecture's module:
the causal half's forward, recompute and backward FLOPs and the bytes
moved, per chip) over ``mla_attention_ms``."""

from chipbench import harness


def read(run):
    ms = harness.reader(run.cell.metrics_dir, "mla_attention_ms")(run)
    least = getattr(run.cell.arch, "mla_least_s", None)
    if not ms or least is None:
        return None
    seconds, _ = least(run.config, run.seq_len, run.tokens_per_step,
                       run.peaks)
    return 100.0 * seconds / run.chips / (ms / 1e3)
