"""The ONE reference every greedy-output test of ``ContinuousBatchingEngine``
compares with: ``models.llama.forward_stacked``'s full re-forward over the
growing sequence — no KV cache, no paging, no packing, float32 logits.

``assert_greedy`` holds served tokens to it exactly. No seed in the tree
shows a near-tie between two logits; one that does would need the
teacher-forced form of ``test_afmoe_serving.py`` (the reference's logit of
the served token within 1e-4 of its maximum) instead."""

import jax.numpy as jnp
import numpy as np

from paddle_tpu.models import llama as L


def greedy_reforward(params, cfg, prompt, n_new):
    """``n_new`` greedy tokens after ``prompt``: argmax of the last
    position's logits of a full forward pass, the sequence grown by one
    token a pass."""
    seq = np.asarray(prompt, np.int32)[None, :]
    out = []
    for _ in range(n_new):
        logits = L.forward_stacked(params, jnp.asarray(seq), cfg)
        nxt = int(np.asarray(jnp.argmax(logits[0, -1].astype(jnp.float32))))
        out.append(nxt)
        seq = np.concatenate([seq, [[nxt]]], axis=1).astype(np.int32)
    return out


def assert_greedy(params, cfg, prompts, outs, n_new=None):
    """Every served token list equals the oracle's, token for token
    (``n_new`` where an output must also have that length, e.g. no EOS)."""
    assert len(prompts) == len(outs)
    for i, (prompt, out) in enumerate(zip(prompts, outs)):
        out = [int(t) for t in out]
        want = greedy_reforward(params, cfg, prompt,
                                len(out) if n_new is None else n_new)
        assert out == want, (i, len(prompt), out, want)
