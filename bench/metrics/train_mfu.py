"""The training window's share of the bf16 peak, in the train_epochs cells
(moves ``train_ratings_per_s``); see bench/train_metrics.py."""
from bench.train_metrics import mfu as read  # noqa: F401
