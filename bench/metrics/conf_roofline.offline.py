"""The fused confidence kernel's share of its bytes-bound roofline, in %
(``bench.readers.conf_roofline``).  Layer: kernels.  Moves
``gen_tok_s``."""
from bench.readers import conf_roofline as read  # noqa: F401
