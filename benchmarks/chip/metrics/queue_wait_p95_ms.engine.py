"""95th percentile of the engine's own queue wait (``engine.queue_wait_s``:
a request's ``arrival_s``, its due time, to the start of the admission
round that took it), over the requests admitted inside the traced slice
only (the histogram's window between the trace's start and end), ms."""


def read(readings, config, peaks):
    window = readings.get("queue_wait_engine")
    if window is None or window.n == 0:
        return None
    return 1e3 * window.quantile(0.95)
