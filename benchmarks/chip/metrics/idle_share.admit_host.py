"""Share of the traced window in which the device was idle while the host
was inside an admission round (``engine.admit`` and its ``.pack``,
``.put``, ``.launch``, ``.sync``, ``.bind`` spans), %."""


def read(readings, config, peaks):
    tr = readings.get("trace")
    if not tr or "idle_by_engine_span" not in tr or tr["window_s"] <= 0:
        return None
    idle = sum(s for span, s in tr["idle_by_engine_span"].items()
               if span.startswith("engine.admit"))
    return 100.0 * idle / tr["window_s"]
