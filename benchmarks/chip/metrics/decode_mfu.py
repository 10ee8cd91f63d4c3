"""The whole decode step's share of the chip's bf16 peak, %: useful
operations of the live rows decoded in the traced window (2 x matrix
parameters per token, attention over each row's real context, the LM
head) over the device time of the decode programs."""

import opcount


def read(readings, config, peaks):
    tr, c = readings.get("trace"), readings.get("counters")
    busy = tr["by_span"].get("step", 0.0) if tr else 0.0
    if not c or busy <= 0 or c["live_rows"] <= 0:
        return None
    ops = opcount.decode_ops(config, c["live_rows"], c["context_sum"])
    return 100.0 * ops / (busy * peaks["bf16_flops_per_s"])
