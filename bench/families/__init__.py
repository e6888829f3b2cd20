"""Model families: everything of the benchmark that depends on the
architecture.

A configuration file (``bench/configs/<config>.json``) names its family
under ``family``, and the harness loads ``bench/families/<family>.py``
by path (``bench.run.load_family``), as it loads the metric readers.  A
new architecture comes in as new files: a configuration, a family, and
the cell's traffic, limits and readers.  A family module gives:

* ``program_config(config)``: the program's ``ModelConfig`` at the
  file's depth, rotary base and mask token; raises ``SetupError`` when
  the program's shapes or kinds are not the file's.
* ``tiny_sizes(sizes, tiny)``: the file's ``sizes`` with the family's
  widths taken from the program's ``-tiny`` preset ``tiny``.
* ``param_shapes(sizes, depth)``: ``{leaf path: bench.weights.Leaf}``,
  each leaf's shape and how it is drawn; ``bench.weights`` draws them.
* the architecture's terms of the operation count (``bench.flops``):
  ``layer_flops(sizes, rows, keys)``, one layer over ``rows`` query rows
  that attend to ``keys`` keys each; ``head_flops(sizes, rows)``;
  ``keys(sizes, lo, rows, total)``, the keys each of the rows
  ``lo..lo+rows`` of a ``total``-row canvas attends to (their mean,
  where the rows differ); and
  ``refresh_flops(sizes, depth, lo, block, total)``, a cache refresh at
  the start of the ``block``-row block at ``lo``.
* the plain float32 reference (``bench.reference.replay`` calls it):
  ``forward_rows(flat, tokens, lo, sizes, rows, fp8=False)``,
  ``capture(flat, tokens, sizes, fp8=False)`` and
  ``forward_window(flat, win_tokens, lo, kv, sizes, fp8=False)``; with
  ``fp8`` the float8 control.
"""


class SetupError(RuntimeError):
    """The run cannot start: no accelerator, a missing file, or a program
    whose shapes differ from the configuration's."""
