"""device_idle_share.sampled: ``bench.readers.device_idle_share``, read in the
sampled cells; moves ``train_targets_per_s.sampled``."""
from bench.readers import device_idle_share as read  # noqa: F401
