"""Output tokens emitted in the window over the window's wall seconds
(host clock): every first token and every decode token of every step."""


def read(rec):
    w = rec["window"]
    return w["tokens"] / w["window_s"]
