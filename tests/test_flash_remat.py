"""The default remat of a decoder layer recomputes everything EXCEPT the flash
forward kernel: ``ops.flash_attention._fa_fwd`` names its ``out`` and ``lse``
residuals, ``build_hybrid_train_step`` saves exactly those two, and the
backward reads them instead of running the S^2 kernel a second time.

No chip here: a ``pallas_call`` TRACES without one, so the programs are
counted in the jaxpr with ``use_pallas`` forced, and run with the kernels in
interpret mode at a small shape.
"""

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import llama as L
from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops import rope as rope_ops
from paddle_tpu.parallel import mesh as pmesh

FLASH = ("flash_attention_fwd", "flash_attention_bwd_dq",
         "flash_attention_bwd_dkv")
S = 512     # the shortest sequence the Pallas branch takes


def _sub_jaxprs(value):
    for v in (value if isinstance(value, (tuple, list)) else (value,)):
        inner = getattr(v, "jaxpr", v)
        if hasattr(inner, "eqns"):
            yield inner


def pallas_calls(jaxpr) -> collections.Counter:
    """``pallas_call`` name -> call sites, through every nested jaxpr."""
    found = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1
            continue
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                found.update(pallas_calls(sub))
    return found


@pytest.fixture
def pallas(monkeypatch):
    """Take the Pallas branch off the chip, kernels in interpret mode (a
    trace never runs them)."""
    monkeypatch.setattr(fa, "use_pallas", lambda: True)
    for name in ("_flash_fwd_pallas", "_flash_bwd_pallas"):
        monkeypatch.setattr(fa, name, functools.partial(
            getattr(fa, name), interpret=True))


def _config(head_dim=128):
    return L.llama_tiny(num_hidden_layers=2, hidden_size=2 * head_dim,
                        num_attention_heads=2, num_key_value_heads=1,
                        intermediate_size=256, vocab_size=256,
                        max_position_embeddings=S)


def _layer(cfg):
    """One decoder layer as the train step calls it, its weights scaled up
    so that attention is a real part of the stream, and an input."""
    params = L.init_stacked_params(cfg, seed=0)
    lp = {k: params[k][0] * (1.0 if k.startswith("ln") else 6.0)
          for k in L.LAYER_KEYS}
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, cfg.hidden_size),
                          jnp.float32)
    cos, sin = rope_ops.build_rope_cache(S, cfg.head_dim, cfg.rope_theta)
    fn = functools.partial(L._decoder_layer_manual, config=cfg, mp_axis=None,
                           fsdp_axis=None, sep_axis=None)
    return fn, lp, x, cos, sin


def _loss_and_grads(checkpointed, lp, x, cos, sin):
    def loss(lp, x):
        return jnp.sum(jnp.square(checkpointed(lp, x, cos, sin)))
    return jax.value_and_grad(loss, argnums=(0, 1))(lp, x)


_SAVE_FLASH = jax.checkpoint_policies.save_only_these_names(
    *fa.SAVED_RESIDUALS)


@pytest.mark.parametrize("policy,forwards", [
    (_SAVE_FLASH, 1),
    # what the default was, and what the test guards against: under a bare
    # checkpoint the backward replays the forward kernel for out and lse
    (None, 2),
], ids=["save_flash_residuals", "bare_checkpoint"])
def test_grad_of_one_checkpointed_layer_forward_call_sites(pallas, policy,
                                                           forwards):
    fn, lp, x, cos, sin = _layer(_config())
    jaxpr = jax.make_jaxpr(functools.partial(
        _loss_and_grads, jax.checkpoint(fn, policy=policy)))(lp, x, cos, sin)
    assert pallas_calls(jaxpr.jaxpr) == {
        "flash_attention_fwd": forwards, "flash_attention_bwd_dq": 1,
        "flash_attention_bwd_dkv": 1}


@pytest.mark.parametrize("remat_policy,forwards", [
    ("full", 1),
    # not this change's: they keep the replay they had (dots saves matmul
    # outputs, offload streams a copy of the attention output and no lse)
    ("dots", 2), ("offload", 2)])
def test_train_step_forward_call_sites(pallas, remat_policy, forwards):
    cfg = _config()
    step, _ = L.build_hybrid_train_step(
        cfg, pmesh.build_mesh({}, devices=jax.devices()[:1]),
        remat_policy=remat_policy)
    params = jax.eval_shape(lambda: L.init_stacked_params(cfg))
    f32 = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), params)
    opt = {"step": jax.ShapeDtypeStruct((), jnp.int32), "m": f32, "v": f32}
    batch = jax.ShapeDtypeStruct((1, 1, S), jnp.int32)
    found = pallas_calls(jax.make_jaxpr(step)(params, opt, batch,
                                              batch).jaxpr)
    assert {k: found[k] for k in FLASH} == {
        "flash_attention_fwd": forwards, "flash_attention_bwd_dq": 1,
        "flash_attention_bwd_dkv": 1}


@pytest.mark.parametrize("head_dim", [64, 128])
def test_saved_residuals_give_the_replays_loss_and_gradients_bit_for_bit(
        pallas, head_dim):
    fn, lp, x, cos, sin = _layer(_config(head_dim))
    run = jax.jit(_loss_and_grads, static_argnums=0)
    loss, (g_lp, g_x) = run(jax.checkpoint(fn, policy=_SAVE_FLASH),
                            lp, x, cos, sin)
    loss0, (g_lp0, g_x0) = run(jax.checkpoint(fn), lp, x, cos, sin)
    assert np.isfinite(float(loss)) and float(loss) > 0
    assert np.asarray(loss).tobytes() == np.asarray(loss0).tobytes()
    assert np.asarray(g_x).tobytes() == np.asarray(g_x0).tobytes()
    for k in L.LAYER_KEYS:
        assert float(jnp.max(jnp.abs(g_lp[k]))) > 0, k
        assert np.asarray(g_lp[k]).tobytes() == \
            np.asarray(g_lp0[k]).tobytes(), k


@pytest.mark.parametrize("remat_policy", ["attn", "everything"])
def test_unknown_remat_policy_lists_the_remaining_ones(remat_policy):
    mesh = pmesh.build_mesh({}, devices=jax.devices()[:1])
    with pytest.raises(ValueError) as err:
        L.build_hybrid_train_step(_config(), mesh, remat_policy=remat_policy)
    assert repr(remat_policy) in str(err.value)
    assert "'full', 'dots' or 'offload'" in str(err.value)


@pytest.mark.parametrize("causal", [True, False])
def test_a_name_outside_a_checkpoint_is_the_identity(pallas, monkeypatch,
                                                     causal):
    """Eager forward + backward of the kernels: the named residuals give
    the reference's output and gradients, and the bytes they give with the
    names taken out."""
    key = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v, g = (jax.random.normal(kk, (2, S, 128), jnp.float32)
                  for kk in key)
    scale = 1.0 / math.sqrt(128)

    def out_and_grads(attn):
        out, vjp = jax.vjp(lambda a, b, c: attn(a, b, c, scale, causal),
                           q, k, v)
        return (out,) + vjp(g)

    named = out_and_grads(fa.flash_attention_bhsd)
    for got, want in zip(named, out_and_grads(fa._attn_ref)):
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    for got, want in zip(named, out_and_grads(fa.flash_attention_bhsd)):
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
