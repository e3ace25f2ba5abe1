"""batch_wait_ms (ms): host time the training loop spent in ``next()``
of the source's batch stream (the benchmark's ``batch_wait`` span: the
sampler, staging and the prefetch queue behind it) over the traced
window, per step.  A source whose batches are constant has none."""


def read(record):
    w = record["window"]
    if not record["batch_bytes"] or not w["steps"]:
        return None
    return 1e3 * record["batch_wait_s"] / w["steps"]
