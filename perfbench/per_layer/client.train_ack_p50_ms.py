"""RPC transport: median of the ``train`` calls answered in the window,
from when each was due (parse, queue and dispatch; the device step is
enqueued, not waited for)."""

from harness import stats

NAME = "client.train_ack_p50_ms"


def read(run):
    return stats.percentile(stats.latencies_ms(run.window("train")), 50)
