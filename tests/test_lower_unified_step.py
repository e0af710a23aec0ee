"""``ContinuousBatchingEngine.lower_unified_step`` lowers the program the
engine serves with over abstract inputs (the benchmark's adapters and the
ahead-of-time chip check call it). Its abstract arguments are written out by
hand beside the ones ``_enqueue`` uploads, so the two can drift apart: for
each served family's tiny configuration they must agree, in tree, shape and
dtype, with what the engine's first ``step()`` hands the compiled unified
program."""

import importlib

import jax
import numpy as np
import pytest

from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)

FAMILIES = ["llama", "afmoe", "axk1", "longcat_flash", "smallthinker",
            "jamba"]


def _signature(tree):
    """A pytree's structure and, leaf by leaf, its shape and dtype."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, [(tuple(a.shape), np.dtype(a.dtype)) for a in leaves]


@pytest.mark.parametrize("family", FAMILIES)
def test_lowered_arguments_are_what_the_first_step_passes(family):
    module = importlib.import_module(f"paddle_tpu.models.{family}")
    cfg = getattr(module, family + "_tiny")()
    params = module.init_stacked_params(cfg, seed=0)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=3), num_slots=2, page_size=4,
        max_seq_len=32, chunk=2)
    lowered = eng.lower_unified_step()
    want_args, want_kwargs = lowered.args_info
    assert not want_kwargs

    passed = []
    build = eng._build_unified_step

    def spying_build(mesh=None):
        program = build(mesh)

        def spy(*args):
            passed.append(_signature(args))
            return program(*args)
        return spy
    eng._build_unified_step = spying_build
    eng.submit(np.arange(1, 6, dtype=np.int32))
    eng.step(params)
    assert len(passed) == 1
    assert passed[0] == _signature(want_args)
    # the lowered program returns what the engine unpacks: the tokens of
    # every micro-round, then the carries and the cache's arrays
    out = jax.tree_util.tree_leaves(lowered.out_info)
    assert out[0].shape == (eng.chunk, eng.num_slots)
    assert len(out) >= 3 + len(eng.mgr.arrays)
