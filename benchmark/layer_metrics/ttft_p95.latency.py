"""Time from the instant a request was DUE (not submitted) to its first
token, 95th percentile over all requests due in the window, a request that
fails or never finishes counting as a miss. Not an end-to-end metric: at 120
requests a window it spreads by 5 to 8 % from run to run (PERF.md)."""


def read(bundle):
    return bundle["values"].get("ttft_p95_ms")
