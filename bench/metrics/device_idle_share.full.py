"""device_idle_share.full: ``bench.readers.device_idle_share``, read in the
full-graph cells; moves ``train_targets_per_s.full``."""
from bench.readers import device_idle_share as read  # noqa: F401
