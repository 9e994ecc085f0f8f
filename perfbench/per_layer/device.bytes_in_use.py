"""Device: the highest ``runtime.jax_device_bytes_in_use`` any server's
sampler showed during the window."""

NAME = "device.bytes_in_use"


def read(run):
    top = max(run.bytes_in_use_max or [0])
    return top if top > 0 and not run.rehearsal else None
