"""An HTTP worker's time from the last byte of a PUT parsed to its record
pushed on the propose ring: `worker_stages.put.edge_in` (api/aio.py
`_do_put`), window mean over the workers the scrapes reached.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.worker_mean_ms(before, after, "put.edge_in")
