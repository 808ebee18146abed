"""An HTTP worker's time from a PUT's completion popped off the completion
ring to its response handed to the transport (ack bridge, event-loop
wake-up, `_finish`): `worker_stages.put.edge_out`, window mean over the
workers the scrapes reached.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.worker_mean_ms(before, after, "put.edge_out")
