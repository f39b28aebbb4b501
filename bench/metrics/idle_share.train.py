"""``idle_share.train``: the share of the traced window in which no
operation ran on the device, during training steps: 1 - busy / window
(``bench/trace.py``). Layer: device. Moves ``train_tokens_per_s``."""


def compute(rec: dict):
    tr = rec["out"].get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
