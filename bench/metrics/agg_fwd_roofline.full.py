"""agg_fwd_roofline.full: ``bench.readers.agg_fwd_roofline``, read in the
full-graph cells; moves ``train_targets_per_s.full``."""
from bench.readers import agg_fwd_roofline as read  # noqa: F401
