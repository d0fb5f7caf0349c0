"""Tokens of every step the window completed, over the whole window."""


def read(run):
    return len(run.window_steps) * run.tokens_per_step / run.window_s
