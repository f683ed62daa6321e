"""Next-token prediction by a model whose attention trains an indexer beside
it: ``next_token``'s rows and targets, and a loss of two terms.

``make`` and ``prepare`` are ``next_token``'s own.  ``forward`` returns
``{"logits": f32[B, S, V], "indexer_kl": f32 scalar}`` (the mean over layers
and query positions of the indexer's KL from the attention's own
distribution, ``reference/keye_vl2.py``); ``loss`` is ``next_token.loss`` of
the logits plus that scalar, at coefficient 1.

A sample is one row, as in ``next_token``.
"""

from . import next_token

make = next_token.make
prepare = next_token.prepare


def loss(outputs, targets):
    return next_token.loss(outputs["logits"], targets) \
        + outputs["indexer_kl"]
