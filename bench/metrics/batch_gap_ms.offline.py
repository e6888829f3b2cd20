"""Median host time at a batch boundary, in ms: from the end of one
batch's last ``device_wait`` to the end of the next batch's
``dispatch[0]`` — finish, emit, selection, assembly and the new
decode's set-up (``bench.stage_gaps``).  Layer: scheduler and engine
(host path).  Moves ``gen_tok_s``."""
from bench.stage_gaps import batch_gap_ms as read  # noqa: F401
