"""Block-diffusion training over packed documents drawn from the seed: a
noisy and a clean copy of every row, and a weighted loss at the masked
positions of the noisy one (BD3-LM, arXiv:2503.09573; the ``1 / t`` weight of
MDLM / LLaDA).

What ``make`` writes (``S = sizes["seq_len"]``, ``B = sizes["block_length"]``,
``V = sizes["vocab_held"]``, ``[MASK] = sizes["mask_id"]``): two int32 arrays
a row, which is all a token loader of the program takes.

==========  ===================  =========================================
key         dtype, shape         what
==========  ===================  =========================================
``x_train``  int32 ``[n, 2 S]``    ``[:, :S]`` the noisy copy: the token's id,
                                 or ``[MASK]`` where it is masked;
                                 ``[:, S:]`` the clean copy: ids in
                                 ``[0, V)`` less ``[MASK]``
``y_train``  int32 ``[n, 2 S]``    ``[:, :S]`` the document number of each
                                 token; ``[:, S:]`` the noise level ``t`` of
                                 the token's block, in 65,536ths
``x_test``   int32 ``[m, 2 S]``    as ``x_train``, from further draws
``y_test``   int32 ``[m, 2 S]``    as ``y_train``; numbers start at 0 again
==========  ===================  =========================================

Each split is one stream of documents packed end to end with no padding and
cut into rows of ``S``, as ``next_token`` packs them and from the same
family (log-normal lengths, median 1,024 tokens, sigma 1.2, clipped to ``[16,
4 S]``; ids a sparse Markov chain of 8 favoured successors taken with
probability 0.7), with two differences: a length is rounded up to a multiple
of ``B``, so that no block straddles two documents, and no id is ever
``[MASK]``.  A row's token ``i`` lies in block ``i // B``.

The noise, drawn here and not by the program, so that the program and the
reference see the same masks and a run is a function of its seed: a block
draws ``t`` uniform over the 65,536ths in ``[noise_t_min, 1]`` (the clipped
linear schedule), and each of its positions is masked with probability ``t``,
independently.  A position is masked iff its noisy id is ``[MASK]``.

``prepare`` gives ``forward`` the dict ``{"ids": [rows, 2 S], "docs": [rows,
S]}`` and ``loss`` ``{"ids", "masked", "weight"}``, each ``[rows, S]``: the
clean id, whether the position is masked, and ``1 / t`` (at most ``1 /
noise_t_min``: 20).  ``loss`` is ``sum(masked x weight x CE(outputs, ids)) /
(rows x S)`` over ``outputs[rows, S, V]``, the logits of the noisy copy: the
position's own id, no shift.

A sample is one row: ``S`` tokens, so tokens a second are
``job_samples_per_s`` times ``seq_len`` (and positions through every layer
twice that).
"""

import jax
import jax.numpy as jnp
import numpy as np

from .next_token import (MEDIAN_TOKENS, P_SUCCESSOR, SHORTEST, SIGMA,
                         SUCCESSORS)

T_UNIT = 65536  # ``t`` is stored as an integer: this many parts make 1


def make(seed: int, n_train: int, n_test: int, config) -> dict:
    z = config["sizes"]
    seq, vocab, block, mask_id = (z["seq_len"], z["vocab_held"],
                                  z["block_length"], z["mask_id"])
    t_low = int(np.ceil(z["noise_t_min"] * T_UNIT))
    if seq % block or mask_id != vocab - 1:
        raise ValueError(f"seq_len {seq} must be a multiple of block_length "
                         f"{block}, and mask_id {mask_id} the last id of "
                         f"{vocab}")
    rng = np.random.default_rng([int(seed), 0xB10C])
    ids_drawn = vocab - 1  # every id but ``[MASK]``, the last
    successors = rng.integers(0, ids_drawn, (ids_drawn, SUCCESSORS),
                              dtype=np.int32)

    def split(n):
        total = n * seq
        # no document is shorter than SHORTEST, so these always fill it
        lengths = np.clip(np.rint(rng.lognormal(
            np.log(MEDIAN_TOKENS), SIGMA, total // SHORTEST + 1)),
            SHORTEST, 4 * seq).astype(np.int64)
        lengths = -(-lengths // block) * block
        lengths = lengths[:np.searchsorted(np.cumsum(lengths), total) + 1]
        docs = np.repeat(np.arange(len(lengths), dtype=np.int32),
                         lengths)[:total]
        fresh = rng.random(total) >= P_SUCCESSOR  # a uniform id here
        fresh[:1] = True
        fresh[1:] |= docs[1:] != docs[:-1]
        which = rng.integers(0, SUCCESSORS, total, dtype=np.int8)
        # every id appears, as far as the uniform draws reach
        n_fresh = int(fresh.sum())
        uniform = np.concatenate([np.arange(ids_drawn), rng.integers(
            0, ids_drawn, max(n_fresh - ids_drawn, 0))])[:n_fresh]
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
        # the noise: a level a block, a coin a position
        t = np.repeat(rng.integers(t_low, T_UNIT + 1, total // block,
                                   dtype=np.int32), block)
        masked = rng.random(total) * T_UNIT < t
        noisy = np.where(masked, np.int32(mask_id), ids)
        rows = lambda a: a.reshape(n, seq)
        return (np.concatenate([rows(noisy), rows(ids)], axis=1),
                np.concatenate([rows(docs), rows(t)], axis=1))

    x_train, y_train = split(n_train)
    x_test, y_test = split(n_test)
    return {"x_train": x_train, "y_train": y_train,
            "x_test": x_test, "y_test": y_test}


def prepare(x_raw, y_raw, config):
    seq = x_raw.shape[1] // 2
    masked = x_raw[:, :seq] == config["sizes"]["mask_id"]
    return ({"ids": x_raw, "docs": y_raw[:, :seq]},
            {"ids": x_raw[:, seq:], "masked": masked,
             "weight": T_UNIT / y_raw[:, seq:].astype(jnp.float32)})


def loss(outputs, targets):
    logits = outputs.astype(jnp.float32)
    picked = jnp.take_along_axis(
        logits, targets["ids"][..., None], axis=-1)[..., 0]
    nll = jax.scipy.special.logsumexp(logits, axis=-1) - picked
    return jnp.sum(jnp.where(targets["masked"], targets["weight"] * nll,
                             0.0)) / targets["ids"].size
