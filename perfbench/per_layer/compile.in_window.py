"""Compile: ``runtime.jax_compile_count`` gained across the window, all
servers; must read 0. The end reading is taken while the stream still
runs, the traffic file's ``run_past_s`` after the window's end, because
the runtime sample behind ``get_status`` is up to 1 s old; the stream's
last flushes, which may have sizes of their own, come after it."""

from harness import stats

NAME = "compile.in_window"


def read(run):
    return sum(stats.counter_delta(s0, s1, "runtime.jax_compile_count")
               for s0, s1 in zip(run.status0, run.status_end))
