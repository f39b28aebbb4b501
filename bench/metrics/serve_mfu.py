"""``serve_mfu``: split serving's share of the chip's bf16 peak over the
measured window: the FLOPs of every request that retired in it (its
prompt prefilled and its tokens generated, each at its own context;
``flops/<config>.py``) / window / peak. Layer: model step (the
scheduler's prefill waves and paged decode blocks). Moves
``serve_tokens_per_s``."""


def compute(rec: dict):
    out, cfg = rec["out"], rec["config"]
    reqs = out.get("flops_args", {}).get("requests")
    if not reqs or not out.get("window_s"):
        return None
    flops = sum(rec["flops"].serve_flops(cfg, p, g) for p, g in reqs)
    return 100.0 * flops / out["window_s"] / rec["peak"]["bf16_flops_per_s"]
