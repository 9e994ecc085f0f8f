"""Step: share of the rows the compiled row buckets ran that were padding:
counters ``step.train.rows_padded`` less ``step.train.rows``, over the
former (8,000 rows run in the 8,192-row program: 2.3%)."""

from harness import reading

NAME = "step.train_pad_share"


def read(run):
    padded = reading.counter(run, "trace.counter.step.train.rows_padded")
    if padded <= 0:
        return None
    rows = reading.counter(run, "trace.counter.step.train.rows")
    return 100.0 * (padded - rows) / padded
