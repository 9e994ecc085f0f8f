"""RPC transport: 95th percentile of the ``train`` calls answered in the
window, from when each was due. Not an end-to-end metric: the server's
quality plane scores 1 train call in 20 against the device before it is
queued, so exactly 5% of the calls take a slow path and the 95th
percentile falls on the edge between the two."""

from harness import stats

NAME = "client.train_ack_p95_ms"


def read(run):
    return stats.percentile(stats.latencies_ms(run.window("train")), 95)
