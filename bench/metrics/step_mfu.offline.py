"""Share of the chip's bf16 peak that each batch's decode attains, in %
(``bench.readers.step_mfu``).  Layer: model step.  Moves
``gen_tok_s``."""
from bench.readers import step_mfu as read  # noqa: F401
