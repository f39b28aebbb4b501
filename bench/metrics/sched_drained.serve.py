"""``sched_drained.serve``: the share of the traced window in which the
serve scheduler's host code ran while none of its compiled programs was
queued on the device: the sum of the program's ``vfl.sched.drained``
spans (``repro.utils.spans``, kept during the traced run calls) over
the traced window. Layer: scheduler (``federation/scheduler.py``).
Moves ``serve_tokens_per_s``."""


def compute(rec: dict):
    tr = rec["out"].get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    try:
        from repro.utils import spans
    except ImportError:         # a program without spans
        return None
    drained = [s for s in spans.spans() if s.name == "vfl.sched.drained"]
    if not drained:
        return None
    return 100.0 * sum(s.seconds for s in drained) / tr["window_s"]
