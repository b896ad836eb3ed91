"""FLOPs the prompts admitted in the window need (``bench/work.py``: no
padding rows, no recomputation) over the engine's prefill device seconds
at the chip's bf16 peak, in %."""
UNIT = "%"


def read(rec):
    dev_s, peak = rec["counters"]["prefill_device_s"], rec.get("peak")
    if not dev_s or not peak:
        return None
    return 100.0 * rec["work"]["prefill_flops"] / (dev_s * peak["bf16_flops"])
