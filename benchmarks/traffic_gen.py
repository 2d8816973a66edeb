"""The one general traffic generator.  A traffic mix is a data file of
parameters under ``traffic/``; this module turns one, with a seed, into the
run's work: today training batches.  Every seed offers the same amount of
work (the same shapes); the seed draws the token ids.

Under a ``world`` (``{"world": {"seed": W}}`` in the traffic file) the seed
draws no id: the ids of every run are the world's, drawn from ``W`` as a
seed's are, and so are the weights (the runner makes them from
``TokenBatches.world_seed``).  The run's seed draws the NAMES: one
permutation of the vocabulary, by which every id is renamed, and for every
step an order of the batch's rows.  A runner moves the weights' tables with
the ids (``move_tables``: the embedding's rows, the head's columns), and the
run then does the world's work under other names: the same loss, the same
routed load, every gradient the world's.  A cell whose step time follows
what the seeded weights and ids route (a mixture of experts) gets runs that
can be compared across seeds that way.
"""

import numpy as np

# ------------------------------------------------------------------ training
def zipf_cdf(vocab, exponent):
    weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    cdf = np.cumsum(weights)
    return cdf / cdf[-1]


class TokenBatches:
    """Seeded next-token batches: ``[B, S]`` ``input_ids`` and ``labels``
    (the ids shifted by one), token ids Zipf-distributed over the vocabulary
    so that there is something to learn (a unigram distribution with entropy
    well under ln V).

    ``world_seed`` is the seed the ids are drawn from and the weights are to
    be made from: the run's own, or the traffic's ``world``.  Under a world
    ``order`` is the run's renaming (the world's id ``i`` is the run's
    ``order[i]``) and ``inverse`` its inverse; without one both are None."""

    def __init__(self, traffic, vocab, seed):
        dist = traffic["token_dist"]
        if dist["kind"] != "zipf":
            raise ValueError(f"unknown token_dist kind {dist['kind']!r}")
        self.cdf = zipf_cdf(vocab, float(dist["exponent"]))
        self.vocab = vocab
        self.shape = (int(traffic["micro_batch"]) * int(
            traffic.get("grad_accum", 1)), int(traffic["seq_len"]) + 1)
        self.seed = int(seed)
        self.world_seed, self.order, self.inverse = self.seed, None, None
        if "world" in traffic:
            self.world_seed = int(traffic["world"]["seed"])
            self.order = self._names(0).permutation(vocab).astype(np.int32)
            self.inverse = np.empty_like(self.order)
            self.inverse[self.order] = np.arange(vocab, dtype=np.int32)

    def _names(self, *key):
        """The run's own stream for what it names: apart from every stream
        ids are drawn from, the world's and any seed's (a spawn key)."""
        return np.random.default_rng(
            np.random.SeedSequence(self.seed, spawn_key=key))

    def batch(self, step):
        rng = np.random.default_rng([self.world_seed, int(step)])
        ids = np.searchsorted(self.cdf, rng.random(self.shape)).astype(np.int32)
        ids = np.minimum(ids, self.vocab - 1)
        if self.order is not None:
            rows = self._names(1, int(step)).permutation(self.shape[0])
            ids = self.order[ids[rows]]
        return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def move_tables(params, index, rows=(), columns=()):
    """A parameter tree whose tables moved with the ids, in one jitted
    gather on the default device: entry ``j`` of a moved table is the old
    table's entry ``index[j]``, along the first axis of the leaves ``rows``
    names (an embedding) and the last axis of those ``columns`` names (a
    head).  A leaf is named by its path, a tuple of keys.  The world's
    weights go to a run's names by ``TokenBatches.inverse``; a run's
    (gradients, say) come back by ``TokenBatches.order``."""
    import jax
    import jax.numpy as jnp

    axis_of = {**{tuple(p): 0 for p in rows},
               **{tuple(p): -1 for p in columns}}
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    axes = [axis_of.pop(tuple(getattr(k, "key", getattr(k, "name", None))
                              for k in path), None) for path, _ in leaves]
    if axis_of:
        raise KeyError(f"no such leaves to move: {sorted(axis_of)}")
    # the tables alone go through the jitted call: a leaf that passed
    # through it untouched would come back as a copy
    out = [x for _, x in leaves]
    picked = [n for n, a in enumerate(axes) if a is not None]
    gather = jax.jit(lambda tables, index: [
        jnp.take(t, index, axis=axes[n]) for t, n in zip(tables, picked)])
    for n, table in zip(picked, gather([out[n] for n in picked],
                                       jnp.asarray(index))):
        out[n] = table
    return jax.tree_util.tree_unflatten(treedef, out)


def own_world(traffic, seed):
    """The traffic with ``seed`` as its world, where it has one: what a
    runner's calibration reads, every seed a world of its own, so that a
    cell's limits stand on many sets of weights and ids."""
    if "world" not in traffic:
        return traffic
    return dict(traffic, world=dict(traffic["world"], seed=int(seed)))
