"""``moe_expert_mfu.serve``: the expert layers' share of the chip's bf16
peak over the traced run calls: the FLOPs of the pairs that were routed
to the held experts and of the shared expert on every token
(``flops/<config>.py``'s ``expert_layer_flops``), from the difference of
the serve scheduler's first and last ``vfl.sched.moe`` records
(``repro.utils.spans``, kept during the traced run calls; each holds the
scheduler's ``moe_counters``), over the time between them. Layer: model
step (``models/moe.py``). Moves ``serve_tokens_per_s``."""


def compute(rec: dict):
    if not rec["out"].get("trace"):
        return None
    try:
        from repro.utils import spans
    except ImportError:         # a program without spans
        return None
    recs = [s for s in spans.spans() if s.name == "vfl.sched.moe"]
    if len(recs) < 2 or recs[-1].end_ns <= recs[0].start_ns:
        return None
    a, b = recs[0].ids, recs[-1].ids
    pairs = int(b["pairs"]) - int(a["pairs"])
    tokens = int(b["tokens"]) - int(a["tokens"])
    seconds = (recs[-1].end_ns - recs[0].start_ns) / 1e9
    flops = rec["flops"].expert_layer_flops(rec["config"], pairs, tokens)
    return 100.0 * flops / seconds / rec["peak"]["bf16_flops_per_s"]
