"""Tokens of all steps in the window over the window's length."""


def read(run):
    r = run.record
    if "tokens" not in r or not r.get("window_s"):
        return None
    return r["tokens"] / r["window_s"]
