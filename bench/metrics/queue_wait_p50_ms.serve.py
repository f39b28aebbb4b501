"""``queue_wait_p50_ms.serve``: the median wait of a request in the
serve scheduler's queue, from joining it to admission: the program's
``vfl.sched.queued`` intervals (``repro.utils.spans``) recorded during
the traced run calls. Their number goes to stderr. Layer: scheduler
(``federation/scheduler.py``). Moves ``serve_latency_p95_ms``."""
import statistics
import sys


def compute(rec: dict):
    tr = rec["out"].get("trace")
    if not tr:
        return None
    try:
        from repro.utils import spans
    except ImportError:         # a program without spans
        return None
    waits = [1e3 * s.seconds for s in spans.spans()
             if s.name == "vfl.sched.queued"]
    if not waits:
        return None
    print(f"queue_wait_p50_ms.serve: median of {len(waits)} admissions",
          file=sys.stderr, flush=True)
    return statistics.median(waits)
