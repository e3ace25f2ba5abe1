"""attn_col_pad_share.full (%): the share of the columns GAT's
attention-aggregation calls read that are padding, 100 × (1 −
``attn_cols`` / ``attn_kernel_cols``), from the counters the program
records where it binds its ELL (``repro.core.tracing``; each head's
column block as the tiled kernel pads it); moves
``train_targets_per_s.full``.  A program or model without the counters
gives None."""
from bench.program_spans import snapshot


def read(record):
    snap = snapshot()
    if snap is None:
        return None
    c = snap["counters"]
    if not c.get("attn_kernel_cols"):
        return None
    return 100.0 * (1.0 - c["attn_cols"] / c["attn_kernel_cols"])
