"""RPC transport: median of the ``classify`` calls answered in the window.
The steadier statistic beside the tail: the 95th percentile is a call that
waited for one train step, the median one that found the device free (or,
near the rate the device sustains, did not)."""

from harness import stats

NAME = "client.classify_p50_ms"


def read(run):
    return stats.percentile(stats.latencies_ms(run.window("classify")), 50)
