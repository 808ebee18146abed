"""A tick's commits handed to a publish worker -> taken up by it
(`stages.publish.queue`, runtime/hostplane.py `_enqueue_publish` ->
`_pub_run`; one sample a worker a tick), window mean: the part of
engine_commit_ms between the durable barrier and the publish.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.engine_mean_ms(before, after, "publish.queue")
