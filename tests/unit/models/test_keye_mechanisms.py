"""The plain reference of Keye (``benchmarks/reference/keye_ref.py``) with one
mechanism left out at a time, at a tiny size on the CPU: each control of the
benchmark's check is another model than the one ``test_keye.py`` holds the
program to."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core
from benchmarks.reference import keye_ref as ref

TINY = core.load_json(core.BENCH_DIR + "/configs/tiny-keye-rehearsal.json")


def _ids(seed, b=2, s=96):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, TINY["vocab_size"], size=(b, s + 1),
                        dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


@pytest.mark.parametrize("mechanism", ref.MECHANISMS)
def test_a_model_without_a_mechanism_is_another_model(mechanism):
    """Each control of the benchmark's check computes something else: by
    its log-probabilities, its chosen keys, its indexer loss or (the input
    not detached) its gradient."""
    params = ref.init_params(TINY, 11)
    ids, labels = _ids(11, b=1)
    lp, kl, _, keys = ref.token_logprobs(params, TINY, ids[0], labels[0])
    lp2, kl2, _, keys2 = ref.token_logprobs(params, TINY, ids[0], labels[0],
                                            without=(mechanism,))
    chosen, chosen2 = (np.asarray(ref.unpack(k, 96)) for k in (keys, keys2))
    if mechanism == "indexer_detach":
        np.testing.assert_allclose(lp2, lp, rtol=1e-6)
        g, g2 = (ref.loss_and_grads(params, TINY, ids, labels,
                                    without=w)[1] for w in ((), (mechanism,)))
        trunk = np.asarray(g2["layers_0"]["attn"]["q_proj"]["kernel"]
                           - g["layers_0"]["attn"]["q_proj"]["kernel"])
        assert np.abs(trunk).max() > 0
    elif mechanism == "indexer_loss":
        assert float(kl) > 0 and float(kl2) == 0
    elif mechanism in ("selection", "topk_halved"):
        assert chosen2.sum() != chosen.sum()
        assert np.abs(np.asarray(lp2 - lp)).max() > 1e-4
    elif mechanism == "indexer_relu":
        assert (chosen2 != chosen).any() and chosen2.sum() == chosen.sum()
    else:
        assert np.abs(np.asarray(lp2 - lp)).max() > 1e-4
    with pytest.raises(ValueError, match="without"):
        ref.token_logprobs(params, TINY, ids[0], labels[0], without=("x",))
