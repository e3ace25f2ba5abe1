"""stage_ms (ms): the self time of the program's ``stage`` span
(``repro.core.tracing``), the worker's padding, gather into the staging
ring and casts of one batch less its ``ring_wait`` for a free slot,
averaged over the traced window's batches; moves
``train_targets_per_s.sampled``."""
from bench.program_spans import mean_span_ms


def read(record):
    return mean_span_ms(record, "stage")
