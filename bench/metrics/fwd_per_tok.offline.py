"""Batched forward passes per generated token of one sequence: the
batch's ``SampleStats.forward_equivalents`` over one request's
``tokens_generated``, averaged over batches.  A foreseeing search step
costs 1 + K.  Layer: decode strategy.  Moves ``gen_tok_s``."""
from bench import spans


def read(run):
    bs = spans.batches(run.records)
    if not bs:
        return None
    return sum(b.forward_equivalents / b.tokens for b in bs) / len(bs)
