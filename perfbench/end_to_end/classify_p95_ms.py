"""95th percentile of all ``classify`` calls answered in the window."""

from harness import stats

NAME = "classify_p95_ms"


def read(run):
    return stats.percentile(stats.latencies_ms(run.window("classify")), 95)
