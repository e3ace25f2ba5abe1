"""agg_pad_share.full (%): the share of the ELL entries one aggregation
call reads that hold no edge, 100 × (1 − ``ell_edges`` / ``ell_slots``),
from the counters the program records where it binds its ELL
(``repro.core.tracing``; slots as the tiled kernel pads them); moves
``train_targets_per_s.full``."""
from bench.program_spans import pad_share


def read(record):
    return pad_share()
