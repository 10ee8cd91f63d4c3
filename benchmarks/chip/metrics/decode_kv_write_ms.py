"""Device self time of the ops under the ``kv_write`` named scope (the
KV-cache write) in the decode programs (``jit_decode_step``,
``jit_decode_horizon``), per token step executed in the traced window
(the engine's ``decode_steps``), ms."""

PROGRAMS = ("jit_decode_step", "jit_decode_horizon")


def read(readings, config, peaks):
    tr, c = readings.get("trace"), readings.get("counters")
    if not tr or not c or c["decode_steps"] <= 0:
        return None
    scoped = [tr.get("by_scope", {}).get(p) for p in PROGRAMS]
    if not any(scoped):
        return None
    return 1e3 * sum(s.get("kv_write", 0.0) for s in scoped
                     if s) / c["decode_steps"]
