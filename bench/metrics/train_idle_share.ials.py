"""Device idle share of the traced window, in the implicit-ALS epoch cells
(moves ``train_ratings_per_s``); see bench/train_metrics.py."""
from bench.train_metrics import idle_share as read  # noqa: F401
