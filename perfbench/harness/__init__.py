"""The benchmark's own code: load generation, launch, plain reference,
trace reduction and the arithmetic of its metrics. Nothing here imports
the program under test or jax (``trace_reduce`` is the one exception for
jax, and runs in a child process pinned to the CPU)."""
