"""Device time of one training step, in the train_epochs cells
(moves ``train_ratings_per_s``); see bench/train_metrics.py."""
from bench.train_metrics import step_us as read  # noqa: F401
