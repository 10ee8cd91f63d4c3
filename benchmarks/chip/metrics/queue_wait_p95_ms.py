"""95th percentile, over the requests due in the window, of the time from
a request's due time to the admission round that took it into a slot
(the host's clock; a request still waiting at the close counts at its
elapsed time), ms."""


def read(readings, config, peaks):
    return readings.get("queue_wait_p95_ms")
