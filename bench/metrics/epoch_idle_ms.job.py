"""Device idle under the trainer's ``repro.trainer.epoch`` spans and their
children but calibrate, per epoch, in ms, in the train_job cells (moves
``job_ratings_per_s``); see bench/program_spans.py."""
from bench.program_spans import epoch_idle_ms as read  # noqa: F401
