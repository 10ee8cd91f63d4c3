"""Device time of the programs launched by the decode hook, per token
step executed in the traced window (the engine's ``decode_steps``)."""


def read(readings, config, peaks):
    tr, c = readings.get("trace"), readings.get("counters")
    if not tr or not c or c["decode_steps"] <= 0:
        return None
    return 1e3 * tr["by_span"].get("step", 0.0) / c["decode_steps"]
