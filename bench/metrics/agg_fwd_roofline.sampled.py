"""agg_fwd_roofline.sampled: ``bench.readers.agg_fwd_roofline``, read in the
sampled cells; moves ``train_targets_per_s.sampled``."""
from bench.readers import agg_fwd_roofline as read  # noqa: F401
