"""Output tokens emitted by the steps of the window, over the window."""


def read(rec):
    return rec["tokens_in_window"] / rec["window_s"]
