"""``serve_occupancy``: the share of decode slot-steps that produced a
token over the window, from the scheduler's own counters:
delta ``generated_tokens`` / (delta ``steps`` x slots). Layer: scheduler
(``federation/scheduler.py``). Moves ``serve_tokens_per_s``."""


def compute(rec: dict):
    c = rec["out"].get("counters", {})
    if not c.get("steps") or "generated_tokens" not in c:
        return None
    return 100.0 * c["generated_tokens"] / (c["steps"] * c["slots"])
