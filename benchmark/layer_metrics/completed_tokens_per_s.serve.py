"""Generated tokens of every request completed in the window over the
window. Not an end-to-end metric: 18 to 21 requests complete in a window and
which ones do moves it by 6 to 8 % from run to run (PERF.md)."""


def read(bundle):
    return bundle["values"].get("serve_tokens_per_s")
