"""``tabular_mfu``: the asynchronous engine's share of the chip's bf16
peak over the measured window: rounds/s x the FLOPs a round needs
(``flops/<config>.py``) / peak. The configuration's float32 products at
``highest`` take six bf16 passes each; the passes are not counted as
work. Layer: model step (``core/async_engine.py``). Moves
``tabular_rounds_per_s``."""


def compute(rec: dict):
    out, cfg = rec["out"], rec["config"]
    rate = out["e2e"].get("tabular_rounds_per_s")
    if not rate:
        return None
    a = out["flops_args"]
    per_round = rec["flops"].round_flops(cfg, a["batch"], a["q"])
    return 100.0 * rate * per_round / rec["peak"]["bf16_flops_per_s"]
