"""sample_ms (ms): the program's ``sample`` span (``repro.core.tracing``),
the sampler's draw of one batch on the prefetch worker, averaged over the
traced window's batches; moves ``train_targets_per_s.sampled``."""
from bench.program_spans import mean_span_ms


def read(record):
    return mean_span_ms(record, "sample")
