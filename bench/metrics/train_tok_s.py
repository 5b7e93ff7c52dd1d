"""Tokens of the training steps completed in the window over its length."""


def read(run):
    if run["kind"] != "train":
        return None
    return run["tokens"] / run["window_s"]
