"""Output tokens produced inside the window over the window's length."""


def read(run):
    r = run.record
    if "out_tokens" not in r or not r.get("window_s"):
        return None
    return r["out_tokens"] / r["window_s"]
