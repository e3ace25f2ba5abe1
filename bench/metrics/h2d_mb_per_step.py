"""h2d_mb_per_step (MB): bytes of the batch pytree that one step puts on
the device, from its leaves' shapes and dtypes; a count, 1 MB = 1e6
bytes.  A source whose batches are constant has none."""


def read(record):
    if not record["batch_bytes"]:
        return None
    return record["batch_bytes"] / 1e6
