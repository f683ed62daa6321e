"""Next-token prediction over packed documents drawn from the seed.

What ``make`` writes (``S = sizes["seq_len"]``, ``V = sizes["vocab_held"]``,
the slice of the vocabulary that this chip holds): the ``.npz`` that a token
loader of the program has to read.

==========  ===================  =========================================
key         dtype, shape         what
==========  ===================  =========================================
``x_train``  int32 ``[n, S + 1]``  token ids in ``[0, V)``
``y_train``  int32 ``[n, S + 1]``  the document number of each position
``x_test``   int32 ``[m, S + 1]``  as ``x_train``, from further draws
``y_test``   int32 ``[m, S + 1]``  as ``y_train``; numbers start at 0 again
==========  ===================  =========================================

Each split is one stream of documents packed end to end with no padding and
cut into rows of ``S + 1``: a document that reaches a row's end goes on in
the next row under the same number.  Document lengths are log-normal (median
1,024 tokens, sigma 1.2) clipped to ``[16, 4 S]``.  Ids follow a sparse
Markov chain: every id has 8 favoured successors drawn from the seed; the
next id is one of them with probability 0.7 and otherwise uniform over the
slice, and a document's first id is uniform.  So the loss can fall, no two
rows are alike, and every id of the slice occurs wherever a split holds
enough uniform draws (about 0.3 of its tokens) to place them.

``prepare`` gives ``forward`` the dict ``{"ids", "docs"}`` of the first ``S``
positions (the document numbers are there for an attention mask; the loss
mask has the same source) and ``loss`` the next id at each of them, or -1
where that id starts a new document and cannot be predicted.  ``loss`` is
the mean over the other positions of float32 softmax cross-entropy of
``outputs[B, S, V]``.

A sample is one row: ``S`` predicted positions, so tokens a second are
``job_samples_per_s`` times ``seq_len``.
"""

import jax
import jax.numpy as jnp
import numpy as np

MEDIAN_TOKENS, SIGMA, SHORTEST = 1024, 1.2, 16
SUCCESSORS, P_SUCCESSOR = 8, 0.7


def make(seed: int, n_train: int, n_test: int, config) -> dict:
    seq, vocab = config["sizes"]["seq_len"], config["sizes"]["vocab_held"]
    rng = np.random.default_rng([int(seed), 0x70CE])
    successors = rng.integers(0, vocab, (vocab, SUCCESSORS), dtype=np.int32)

    def split(n):
        total = n * (seq + 1)
        # no document is shorter than SHORTEST, so these always fill it
        lengths = np.clip(np.rint(rng.lognormal(
            np.log(MEDIAN_TOKENS), SIGMA, total // SHORTEST + 1)),
            SHORTEST, 4 * seq).astype(np.int64)
        lengths = lengths[:np.searchsorted(np.cumsum(lengths), total) + 1]
        docs = np.repeat(np.arange(len(lengths), dtype=np.int32),
                         lengths)[:total]
        fresh = rng.random(total) >= P_SUCCESSOR  # a uniform id here
        fresh[:1] = True
        fresh[1:] |= docs[1:] != docs[:-1]
        which = rng.integers(0, SUCCESSORS, total, dtype=np.int8)
        # every id appears, as far as the uniform draws reach
        n_fresh = int(fresh.sum())
        uniform = np.concatenate([np.arange(vocab), rng.integers(
            0, vocab, max(n_fresh - vocab, 0))])[:n_fresh]
        rng.shuffle(uniform)
        ids = np.zeros(total, np.int32)
        ids[fresh] = uniform
        # a position's id needs its predecessor's: fill by the distance
        # from the last uniform draw, all positions of one distance at once
        at = np.arange(total)
        depth = at - np.maximum.accumulate(np.where(fresh, at, 0))
        order = np.argsort(depth, kind="stable")
        ends = np.cumsum(np.bincount(depth))
        for lo, hi in zip(ends[:-1], ends[1:]):
            pos = order[lo:hi]
            ids[pos] = successors[ids[pos - 1], which[pos]]
        return ids.reshape(n, seq + 1), docs.reshape(n, seq + 1)

    x_train, y_train = split(n_train)
    x_test, y_test = split(n_test)
    return {"x_train": x_train, "y_train": y_train,
            "x_test": x_test, "y_test": y_test}


def prepare(x_raw, y_raw, config):
    same_document = y_raw[:, 1:] == y_raw[:, :-1]
    return ({"ids": x_raw[:, :-1], "docs": y_raw[:, :-1]},
            jnp.where(same_document, x_raw[:, 1:], -1))


def loss(outputs, targets):
    logits = outputs.astype(jnp.float32)
    judged = targets >= 0
    picked = jnp.take_along_axis(
        logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    nll = jax.scipy.special.logsumexp(logits, axis=-1) - picked
    return jnp.sum(jnp.where(judged, nll, 0.0)) / jnp.maximum(
        jnp.sum(judged), 1)
