"""Device idle under the engine's ``repro.serving.fetch`` spans per
``repro.serving.launch`` span, in ms, in the batch cells (moves
``batch_users_per_s``); see bench/program_spans.py."""
from bench.program_spans import fetch_idle_ms as read  # noqa: F401
