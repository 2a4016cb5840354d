"""Blocking device->host reads per launch, on the dse.dispatch and dse.harvest spans."""
from program_spans import syncs_per_launch


def read(run):
    return syncs_per_launch(run)
