"""Device idle under the trainer's ``repro.trainer.init`` and
``repro.trainer.calibrate`` spans per trainer constructed in the window, in
ms, in the train_job cells (moves ``job_ratings_per_s``); see
bench/program_spans.py."""
from bench.program_spans import job_idle_ms as read  # noqa: F401
