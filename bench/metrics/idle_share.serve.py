"""``idle_share.serve``: the share of the traced window (``trace_calls``
run calls of the closed loop, callers still submitting) in which no
operation ran on the device: 1 - busy / window (``bench/trace.py``).
Layer: device. Moves ``serve_tokens_per_s``."""


def compute(rec: dict):
    tr = rec["out"].get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
