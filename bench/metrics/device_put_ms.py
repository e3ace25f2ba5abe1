"""device_put_ms (ms): the program's ``device_put`` span
(``repro.core.tracing``), the training loop's transfer call for one
staged batch, averaged over the traced window's batches; moves
``train_targets_per_s.sampled``."""
from bench.program_spans import mean_span_ms


def read(record):
    return mean_span_ms(record, "device_put")
