"""``moe_load_max_mean.serve``: how uneven the held experts' load is in
the prefill waves of the traced run calls: over each wave step (one
chunk of a wave through one expert layer), the busiest held expert's
pairs, summed, over the held experts' mean pairs, summed (1 is even).
From the difference of the serve scheduler's first and last
``vfl.sched.moe`` records (``repro.utils.spans``; ``wave_load_max``,
``wave_load_sum`` and ``held`` of its ``moe_counters``). Layer: model
step (``models/moe.py``). Moves ``serve_tokens_per_s``."""


def compute(rec: dict):
    if not rec["out"].get("trace"):
        return None
    try:
        from repro.utils import spans
    except ImportError:         # a program without spans
        return None
    recs = [s for s in spans.spans() if s.name == "vfl.sched.moe"]
    if len(recs) < 2:
        return None
    a, b = recs[0].ids, recs[-1].ids
    top = int(b["wave_load_max"]) - int(a["wave_load_max"])
    total = int(b["wave_load_sum"]) - int(a["wave_load_sum"])
    if total <= 0:
        return None
    return top * int(b["held"]) / total
