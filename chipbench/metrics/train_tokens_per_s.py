"""Tokens of all steps finished in the window over the whole window."""


def read(run):
    return run.window_steps * run.tokens_per_step / run.window_s
