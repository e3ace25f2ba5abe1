"""queue_wait_ms (ms): the program's ``queue_wait`` span
(``repro.core.tracing``), the training loop's blocking get on the prefetch
queue (the time it waits on the worker), averaged over the traced
window's batches; moves ``train_targets_per_s.sampled``."""
from bench.program_spans import mean_span_ms


def read(record):
    return mean_span_ms(record, "queue_wait")
