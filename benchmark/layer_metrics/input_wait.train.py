"""Share of the window the training loop spent inside ``next()`` of the
loader the benchmark handed to ``Trainer`` (host clock)."""


def read(bundle):
    c = bundle["counters"]
    if not c.get("batches"):
        return None
    return 100.0 * c["input_wait_s"] / bundle["window_s"]
