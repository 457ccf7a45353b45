"""90th percentile of the program's queue wait (submit to prefill start,
``RequestMetrics.queue_wait``) of the requests submitted in the window."""
from chipbench.readers import queue_wait_p90_ms as read  # noqa: F401
