"""Vision Transformer (ViT) on the fused attention/FFN blocks.

Workload #5's transformer-vision surface (SURVEY.md §6: ViT-L is one of
the five benchmark configs). Pre-LN encoder built from the same fused
incubate blocks as the language models — patch embedding is a strided
Conv2D (one MXU matmul per patch grid), class token + learned positions,
mean/cls pooling head. Reference surface: the model-zoo
VisionTransformer family.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from ...incubate.nn.layer.fused_transformer import (
    FusedFeedForward, FusedMultiHeadAttention)
from ...nn import functional as F
from ...nn import initializer as I
from ...nn.common_layers import Conv2D, LayerNorm, Linear
from ...nn.layer import Layer, LayerList


class PatchEmbed(Layer):
    def __init__(self, img_size=224, patch_size=16, in_chans=3, embed_dim=768):
        super().__init__()
        if img_size % patch_size:
            raise ValueError("img_size must divide by patch_size")
        self.num_patches = (img_size // patch_size) ** 2
        self.proj = Conv2D(in_chans, embed_dim, kernel_size=patch_size,
                           stride=patch_size)

    def forward(self, x):
        x = self.proj(x)                       # (B, E, H/p, W/p)
        b, e = x.shape[0], x.shape[1]
        return x.reshape([b, e, -1]).transpose([0, 2, 1])  # (B, N, E)


class ViTEncoderLayer(Layer):
    def __init__(self, embed_dim, num_heads, mlp_ratio=4.0, epsilon=1e-6):
        super().__init__()
        self.attn = FusedMultiHeadAttention(
            embed_dim, num_heads, normalize_before=True, epsilon=epsilon)
        self.ffn = FusedFeedForward(
            embed_dim, int(embed_dim * mlp_ratio), activation="gelu",
            normalize_before=True, epsilon=epsilon)

    def forward(self, x):
        return self.ffn(self.attn(x, causal=False))


class VisionTransformer(Layer):
    """ViT backbone + classification head (class_num=0 → features only)."""

    def __init__(self, img_size=224, patch_size=16, in_chans=3,
                 class_num=1000, embed_dim=768, depth=12, num_heads=12,
                 mlp_ratio=4.0, epsilon=1e-6, representation_size=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.patch_embed = PatchEmbed(img_size, patch_size, in_chans,
                                      embed_dim)
        n = self.patch_embed.num_patches
        self.cls_token = self.create_parameter(
            (1, 1, embed_dim), default_initializer=I.Normal(0.0, 0.02))
        self.pos_embed = self.create_parameter(
            (1, n + 1, embed_dim), default_initializer=I.Normal(0.0, 0.02))
        self.blocks = LayerList([
            ViTEncoderLayer(embed_dim, num_heads, mlp_ratio, epsilon)
            for _ in range(depth)])
        self.norm = LayerNorm(embed_dim, epsilon=epsilon)
        self.head = (Linear(embed_dim, class_num) if class_num > 0 else None)

    def forward_features(self, x):
        from ...core.dispatch import apply
        x = self.patch_embed(x)

        def add_tokens(xv, cls, pos):
            b = xv.shape[0]
            cls_b = jnp.broadcast_to(cls, (b,) + cls.shape[1:])
            return jnp.concatenate([cls_b, xv], axis=1) + pos

        x = apply(add_tokens, x, self.cls_token, self.pos_embed,
                  op_name="vit_tokens")
        for blk in self.blocks:
            x = blk(x)
        return self.norm(x)

    def forward(self, x):
        feats = self.forward_features(x)
        cls = feats[:, 0]
        return self.head(cls) if self.head is not None else cls


def vit_base_patch16_224(**kwargs):
    return VisionTransformer(img_size=224, patch_size=16, embed_dim=768,
                             depth=12, num_heads=12, **kwargs)


def vit_large_patch16_224(**kwargs):
    return VisionTransformer(img_size=224, patch_size=16, embed_dim=1024,
                             depth=24, num_heads=16, **kwargs)


def vit_tiny_test(**kwargs):
    """Small config for tests/CI."""
    base = dict(img_size=16, patch_size=4, in_chans=3, class_num=10,
                embed_dim=32, depth=2, num_heads=4)
    base.update(kwargs)
    return VisionTransformer(**base)


# ===========================================================================
# Functional stacked path (round 4): lax.scan over the encoder stack
# ===========================================================================
# The imperative module above runs ~400 separate parameter tensors through
# ~838 XLA fusions per train step (round-4 profile) — per-tensor optimizer
# updates and per-layer kernel launches cap the measured MFU near 41%. The
# stacked form is the same TPU-first design the llama flagship uses
# (models/llama.py): per-layer weights stack on a leading L axis, the
# encoder runs as ONE lax.scan, and AdamW updates ~16 fused arrays.

import jax
from jax import lax

VIT_LAYER_KEYS = ("ln1_s", "ln1_b", "qkv_w", "qkv_b", "out_w", "out_b",
                  "ln2_s", "ln2_b", "f1_w", "f1_b", "f2_w", "f2_b")


def stacked_params_from_module(net: "VisionTransformer") -> dict:
    """Stack a VisionTransformer module's weights into the functional
    layout (leading L axis on per-layer tensors)."""
    # COPY leaves: the train step donates its params, and aliasing the
    # module's live buffers would invalidate the module after one step
    g = lambda p: jnp.array(p._value, copy=True)
    out = {
        "patch_w": g(net.patch_embed.proj.weight),
        "patch_b": g(net.patch_embed.proj.bias),
        "cls": g(net.cls_token),
        "pos": g(net.pos_embed),
        "ln_f_s": g(net.norm.weight),
        "ln_f_b": g(net.norm.bias),
    }
    if net.head is not None:
        out["head_w"] = g(net.head.weight)
        out["head_b"] = g(net.head.bias)
    per = {k: [] for k in VIT_LAYER_KEYS}
    for blk in net.blocks:
        a, f = blk.attn, blk.ffn
        per["ln1_s"].append(g(a.pre_ln_scale))
        per["ln1_b"].append(g(a.pre_ln_bias))
        per["qkv_w"].append(g(a.qkv_weight))
        per["qkv_b"].append(g(a.qkv_bias))
        per["out_w"].append(g(a.linear_weight))
        per["out_b"].append(g(a.linear_bias))
        per["ln2_s"].append(g(f.ln_scale))
        per["ln2_b"].append(g(f.ln_bias))
        per["f1_w"].append(g(f.w1))
        per["f1_b"].append(g(f.b1))
        per["f2_w"].append(g(f.w2))
        per["f2_b"].append(g(f.b2))
    for k, vs in per.items():
        out[k] = jnp.stack(vs)
    return out


def vit_forward_stacked(params, x, num_heads: int, patch: int = 16,
                        eps: float = 1e-6, remat: str = "dots"):
    """(B, C, H, W) -> logits (or cls features when no head). Same math as
    VisionTransformer.forward over the stacked layout.

    ``remat='dots'`` checkpoints the scan body saving only matmul outputs:
    without it the scan hoists six (L, B, S, ff) activation stacks (>7 GB
    at ViT-L B=32) for the backward; recomputing just the elementwise ops
    (LN, gelu) costs negligible FLOPs. 'off' disables."""
    from ...ops import fused_transformer_block as ftb

    b = x.shape[0]
    dn = lax.conv_dimension_numbers(x.shape, params["patch_w"].shape,
                                    ("NCHW", "OIHW", "NCHW"))
    p = lax.conv_general_dilated(
        x, params["patch_w"].astype(x.dtype), (patch, patch), "VALID",
        dimension_numbers=dn)
    p = p + params["patch_b"].astype(x.dtype)[None, :, None, None]
    e = p.shape[1]
    tok = p.reshape(b, e, -1).transpose(0, 2, 1)            # (B, N, E)
    cls = jnp.broadcast_to(params["cls"].astype(x.dtype),
                           (b, 1, e))
    h = jnp.concatenate([cls, tok], axis=1) + params["pos"].astype(x.dtype)

    def body(carry, lp):
        xc = carry
        xn = ftb.layer_norm_array(xc, lp["ln1_s"], lp["ln1_b"], eps)
        qkv = xn @ lp["qkv_w"].astype(xn.dtype) + lp["qkv_b"].astype(xn.dtype)
        q, k, v = ftb._split_heads(qkv, num_heads)
        attn = ftb._prefill_attention(q, k, v, None, causal=False)
        bb, s, _ = xc.shape
        attn = attn.transpose(0, 2, 1, 3).reshape(bb, s, -1)
        xc = xc + (attn @ lp["out_w"].astype(attn.dtype)
                   + lp["out_b"].astype(attn.dtype)).astype(xc.dtype)
        xn = ftb.layer_norm_array(xc, lp["ln2_s"], lp["ln2_b"], eps)
        f = jax.nn.gelu(xn @ lp["f1_w"].astype(xn.dtype)
                        + lp["f1_b"].astype(xn.dtype))
        xc = xc + (f @ lp["f2_w"].astype(f.dtype)
                   + lp["f2_b"].astype(f.dtype)).astype(xc.dtype)
        return xc, None

    layer_stack = {k: params[k] for k in VIT_LAYER_KEYS}
    if remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_saveable)
    h, _ = lax.scan(body, h, layer_stack)
    h = ftb.layer_norm_array(h, params["ln_f_s"], params["ln_f_b"], eps)
    cls_feat = h[:, 0]
    if "head_w" in params:
        return (cls_feat @ params["head_w"].astype(cls_feat.dtype)
                + params["head_b"].astype(cls_feat.dtype))
    return cls_feat


def build_vit_train_step(num_heads: int, patch: int = 16, eps: float = 1e-6,
                         learning_rate: float = 1e-4, dtype=jnp.bfloat16,
                         remat: str = "dots"):
    """Compiled single-device ViT train step over stacked params: fused
    AdamW on ~16 stacked arrays instead of ~400 module tensors (same
    optimizer hyperparameters as the llama flagship step)."""
    b1, b2, adam_eps, wd = 0.9, 0.999, 1e-8, 0.01

    def init_opt(params):
        return {"step": jnp.zeros((), jnp.int32),
                "m": jax.tree_util.tree_map(
                    lambda v: jnp.zeros_like(v, jnp.float32), params),
                "v": jax.tree_util.tree_map(
                    lambda v: jnp.zeros_like(v, jnp.float32), params)}

    def loss_fn(params, x, y):
        logits = vit_forward_stacked(params, x.astype(dtype), num_heads,
                                     patch, eps,
                                     remat=remat).astype(jnp.float32)
        lse = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(lse, y[:, None], axis=1)[:, 0]
        return jnp.mean(nll)

    def step(params, opt_state, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        t = opt_state["step"] + 1

        def upd(p, g, m, v):
            g32 = g.astype(jnp.float32)
            m2 = b1 * m + (1 - b1) * g32
            v2 = b2 * v + (1 - b2) * g32 * g32
            mh = m2 / (1 - b1 ** t.astype(jnp.float32))
            vh = v2 / (1 - b2 ** t.astype(jnp.float32))
            p2 = p.astype(jnp.float32) - learning_rate * (
                mh / (jnp.sqrt(vh) + adam_eps)
                + wd * p.astype(jnp.float32))
            return p2.astype(p.dtype), m2, v2

        new_p, new_m, new_v = {}, {}, {}
        for k in params:
            new_p[k], new_m[k], new_v[k] = upd(
                params[k], grads[k], opt_state["m"][k], opt_state["v"][k])
        return loss, new_p, {"step": t, "m": new_m, "v": new_v}

    return jax.jit(step, donate_argnums=(0, 1)), init_opt
