"""Device: the allocator's high-water mark since the server started
(``runtime.jax_device_peak_bytes_in_use``: ``peak_bytes_in_use`` of
``Device.memory_stats()``, the fullest local device's), the largest of
the run's status samples. It holds what the allocator handed out
between two of the one-second samples that ``device.bytes_in_use`` reads
(a flush's uploaded inputs, a mix round's buffers), and no compiled
step's temporaries: ``memory_stats()`` leaves those out, so where
nothing but flushes allocates it reads ``device.bytes_in_use`` plus a
flush's inputs."""

NAME = "device.peak_bytes_in_use"


def read(run):
    top = max((int(st.get("runtime.jax_device_peak_bytes_in_use", 0) or 0)
               for sts in (run.status0, run.status1, run.status_end)
               for st in sts), default=0)
    return top if top > 0 and not run.rehearsal else None
