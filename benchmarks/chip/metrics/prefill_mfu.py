"""Useful operations of the prompts admitted in the traced window (real
prompt tokens only: no padding row or position), over the device time of
the admission programs times the chip's bf16 peak, %."""


def read(readings, config, peaks):
    tr, c = readings.get("trace"), readings.get("counters")
    busy = tr["by_span"].get("admit", 0.0) if tr else 0.0
    if not c or busy <= 0 or c["prefill_ops"] <= 0:
        return None
    return 100.0 * c["prefill_ops"] / (busy * peaks["bf16_flops_per_s"])
