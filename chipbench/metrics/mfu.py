"""Model FLOP utilization of the whole train step: the model FLOPs per
token (``chipbench/flops.py``) times the window's tokens per second, over
the chips' bf16 peak (``chipbench/peaks.json``)."""

from chipbench import flops


def read(run):
    tok_s = run.window_steps * run.tokens_per_step / run.window_s
    per_tok = flops.train_per_token(run.config, run.seq_len)
    return 100.0 * per_tok * tok_s / (run.chips
                                      * run.peaks["bf16_flops_per_s"])
