"""Share of the traced window in which no operation ran on the chip, in %
(``bench.readers.idle_share``).  Layer: device.  Moves
``gen_tok_s``."""
from bench.readers import idle_share as read  # noqa: F401
