"""95th percentile of service dispatch minus service submit over the window's requests, in ms."""
from program_spans import wait_p95_ms


def read(run):
    return wait_p95_ms(run)
