"""The training step's share of its roofline, in the train_job cells
(moves ``job_ratings_per_s``); see bench/train_metrics.py."""
from bench.train_metrics import step_roofline as read  # noqa: F401
