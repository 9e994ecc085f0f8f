"""RPC transport, generator clock: median of all ``train`` calls answered in
the window (in a saturated cell this is queue depth over rate)."""

from harness import stats

NAME = "client.train_p50_ms"


def read(run):
    return stats.percentile(stats.latencies_ms(run.window("train")), 50)
