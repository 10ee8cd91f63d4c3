"""Share of the token positions the admission prefills computed in the
traced slice that were padding: 1 - ``admit_real_tokens`` /
``admit_positions`` (the engine's counters; rows x bucket per packed
round), %."""


def read(readings, config, peaks):
    c = readings.get("counters")
    if not c or not c.get("admit_positions"):
        return None
    return 100.0 * (1.0 - c["admit_real_tokens"] / c["admit_positions"])
