"""Device idle under the engine's ``repro.serving.gather`` and
``repro.serving.launch`` spans and ``repro.serving.topk``'s own time per
launch, in ms, in the batch cells (moves ``batch_users_per_s``); see
bench/program_spans.py."""
from bench.program_spans import dispatch_idle_ms as read  # noqa: F401
