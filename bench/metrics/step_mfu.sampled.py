"""step_mfu.sampled: ``bench.readers.step_mfu``, read in the
sampled cells; moves ``train_targets_per_s.sampled``."""
from bench.readers import step_mfu as read  # noqa: F401
