"""Median host time at a block boundary inside a batch, in ms: from the
end of ``device_wait[i]`` to the end of ``dispatch[i+1]``, the time in
which the chip has no next block to run (``bench.stage_gaps``).  Layer:
scheduler and engine (host path).  Moves ``gen_tok_s``."""
from bench.stage_gaps import block_gap_ms as read  # noqa: F401
