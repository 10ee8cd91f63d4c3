"""Device time of the programs launched by admission (the padded batched
prefill and its scatter into the slot cache) over the traced window, %."""


def read(readings, config, peaks):
    tr = readings.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * tr["by_span"].get("admit", 0.0) / tr["window_s"]
