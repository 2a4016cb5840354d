"""95th percentile of submit to dispatch over the window's requests, in ms."""
from layer_metrics import queue_wait_p95_ms


def read(run):
    return queue_wait_p95_ms(run)
