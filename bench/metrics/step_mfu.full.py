"""step_mfu.full: ``bench.readers.step_mfu``, read in the
full-graph cells; moves ``train_targets_per_s.full``."""
from bench.readers import step_mfu as read  # noqa: F401
