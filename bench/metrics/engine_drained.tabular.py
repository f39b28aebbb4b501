"""``engine_drained.tabular``: the share of the traced window (one
chunk) in which the asynchronous engine's host code ran with nothing of
the engine queued on the device: the sum of the program's
``vfl.engine.drained`` spans (``repro.utils.spans``: the chunk's
preparation, and its collection after the last blocking read) over the
traced window. Layer: model step (``core/async_engine.py``). Moves
``tabular_rounds_per_s``."""


def compute(rec: dict):
    tr = rec["out"].get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    try:
        from repro.utils import spans
    except ImportError:         # a program without spans
        return None
    drained = [s for s in spans.spans() if s.name == "vfl.engine.drained"]
    if not drained:
        return None
    return 100.0 * sum(s.seconds for s in drained) / tr["window_s"]
