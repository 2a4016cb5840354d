"""Host ms per launch in dispatch and harvest, less the wait for the device."""
from layer_metrics import host_ms_per_launch


def read(run):
    return host_ms_per_launch(run)
