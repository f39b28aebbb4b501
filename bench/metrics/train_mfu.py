"""``train_mfu``: the cascaded training step's share of the chip's bf16
peak, over the measured window: tokens/s x the FLOPs per token the
algorithm needs (``flops/<config>.py``) / peak. Layer: model step
(``core/cascade.py``). Moves ``train_tokens_per_s``."""


def compute(rec: dict):
    out, cfg = rec["out"], rec["config"]
    rate = out["e2e"].get("train_tokens_per_s")
    if not rate:
        return None
    a = out["flops_args"]
    per_token = rec["flops"].train_flops_per_token(cfg, a["seq"], a["q"])
    return 100.0 * rate * per_token / rec["peak"]["bf16_flops_per_s"]
