"""On-chip perf rows for the remaining BASELINE.md workloads (VERDICT
round-2 item 2):

* ``bert``   — workload #3: BERT-large-geometry MLM pretraining step over
               the FusedMultiHeadAttention/FusedFeedForward encoder path.
* ``moe``    — workload #4: GPT-MoE causal-LM train step, dense single-chip
               expert path (the all_to_all path needs a mesh; its dryrun is
               driver config 3).
* ``decode_cb`` — serving: ``ContinuousBatchingEngine`` over the paged KV
               pool (the AnalysisPredictor-replacement path).

Run on the real chip:  python benchmarks/bench_workloads.py [bert|moe|decode_cb]
CPU smoke:             JAX_PLATFORMS=cpu BENCH_WORKLOADS_SMOKE=1 python ...
Timing fences through a device->host transfer (float(...)).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import device_peaks  # noqa: E402 — chip table lives in bench.py


def _peak_flops():
    import jax
    return device_peaks(jax.devices()[0].device_kind)["bf16_flops"]


def _setup():
    import jax

    from paddle_tpu.ops._common import is_tpu_platform

    platform = jax.devices()[0].platform
    smoke = os.environ.get("BENCH_WORKLOADS_SMOKE") == "1" or \
        not is_tpu_platform(platform)
    return jax, smoke


def bench_bert():
    jax, smoke = _setup()
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.models.ernie import ErnieConfig, ErnieForMaskedLM

    if smoke:
        cfg = ErnieConfig(vocab_size=512, hidden_size=64,
                          num_hidden_layers=2, num_attention_heads=4,
                          intermediate_size=128, max_position_embeddings=64)
        B, S, steps, warm = 2, 32, 2, 1
    else:
        # BERT-large geometry (workload #3 reference config)
        cfg = ErnieConfig(vocab_size=30522, hidden_size=1024,
                          num_hidden_layers=24, num_attention_heads=16,
                          intermediate_size=4096,
                          max_position_embeddings=512)
        B, S, steps, warm = 16, 512, 10, 2

    paddle.seed(0)
    net = ErnieForMaskedLM(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=net.parameters())
    if not smoke:
        amp.decorate(models=net, optimizers=opt, level="O2",
                     dtype="bfloat16")

    def loss_fn(model, ids, labels):
        return model.compute_loss(ids, labels)

    step = paddle.jit.TrainStep(net, loss_fn, opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32))
    labels = rng.randint(0, cfg.vocab_size, (B, S))
    labels[rng.rand(B, S) > 0.15] = -100       # MLM: 15% positions scored
    labels = paddle.to_tensor(labels.astype(np.int64))

    for _ in range(warm):
        loss = step(ids, labels)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids, labels)
    float(loss)
    dt = time.perf_counter() - t0

    tok_s = B * S * steps / dt
    n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
    embed = cfg.vocab_size * cfg.hidden_size
    # 6 flops/param/token on matmul params: the embedding GATHER is free,
    # but the tied MLM head re-uses that same matrix as a real projection
    # matmul, so the embed params stay in the count — net n_params.
    # Plus bidirectional attention 12·L·S·h
    n_matmul = n_params
    flops_tok = 6.0 * n_matmul + 12.0 * cfg.num_hidden_layers * S * cfg.hidden_size
    mfu = flops_tok * tok_s / _peak_flops() if not smoke else 0.0
    return {"metric": "bert_large_mlm_train", "tokens_per_sec": round(tok_s, 1),
            "step_ms": round(dt / steps * 1e3, 1), "mfu": round(mfu, 4),
            "params_m": round(n_params / 1e6, 1), "loss": float(loss)}


def _kstep_runner(step, batch_values, kstep):
    """k TRAINING STEPS per host fence (VERDICT r4 #3/#7) — amortizes
    the per-step dispatch + host plumbing that wall-clock MFU otherwise
    pays per step. Now a thin wrapper over the public
    ``TrainStep.multi_step(k)`` API (paddle_tpu/jit): the bench repeats
    ONE batch k times along the required leading axis."""
    import jax.numpy as jnp
    import paddle_tpu as paddle

    run_k = step.multi_step(kstep)
    stacked = tuple(paddle.to_tensor(jnp.stack([v] * kstep))
                    for v in batch_values)

    def run():
        return run_k(*stacked)

    return run


def bench_bert_packed():
    """Workload #3 with sequence packing (VERDICT r3 item 1): ragged
    pretraining sequences packed into full rows, segment-masked Pallas
    flash attention, per-segment loss masking. MFU counts REAL tokens and
    per-segment attention FLOPs only — padding waste shows up as lost MFU,
    exactly as it would on the reference's flash_attn_varlen path."""
    jax, smoke = _setup()
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.models.ernie import ErnieConfig, ErnieForMaskedLM

    if smoke:
        cfg = ErnieConfig(vocab_size=512, hidden_size=64,
                          num_hidden_layers=2, num_attention_heads=4,
                          intermediate_size=128, max_position_embeddings=64)
        B, S, steps, warm = 2, 32, 2, 1
        lo, hi = 8, 32
    else:
        cfg = ErnieConfig(vocab_size=30522, hidden_size=1024,
                          num_hidden_layers=24, num_attention_heads=16,
                          intermediate_size=4096,
                          max_position_embeddings=512)
        B, S, steps, warm = 16, 512, 10, 2
        lo, hi = 64, 512

    paddle.seed(0)
    net = ErnieForMaskedLM(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=net.parameters())
    if not smoke:
        amp.decorate(models=net, optimizers=opt, level="O2",
                     dtype="bfloat16")

    def loss_fn(model, ids, labels, seg):
        return model.compute_loss(ids, labels, segment_ids=seg)

    step = paddle.jit.TrainStep(net, loss_fn, opt)

    # ragged corpus: first-fit-decreasing pack into B rows of S
    rng = np.random.RandomState(0)
    lens = []
    while True:
        n = int(rng.randint(lo, hi + 1))
        if sum(lens) + n > B * S:
            break
        lens.append(n)
    lens.sort(reverse=True)
    fill = [0] * B
    seg_lens = [[] for _ in range(B)]
    for n in lens:
        r = min((i for i in range(B) if fill[i] + n <= S),
                key=lambda i: fill[i], default=None)
        if r is None:
            continue
        seg_lens[r].append(n)
        fill[r] += n
    ids = np.zeros((B, S), np.int32)
    seg = np.full((B, S), -1, np.int32)
    labels = np.full((B, S), -100, np.int64)
    for r in range(B):
        at = 0
        for si, n in enumerate(seg_lens[r]):
            tok = rng.randint(1, cfg.vocab_size, (n,))
            ids[r, at:at + n] = tok
            seg[r, at:at + n] = si
            mask = rng.rand(n) < 0.15          # MLM: 15% positions scored
            labels[r, at:at + n] = np.where(mask, tok, -100)
            at += n
    real_tokens = int((seg >= 0).sum())
    # per-segment bidirectional attention FLOPs: 12*L*h*sum(s_i^2)
    attn_flops = 12.0 * cfg.num_hidden_layers * cfg.hidden_size * float(
        sum(n * n for r in seg_lens for n in r))

    ids_t = paddle.to_tensor(ids)
    labels_t = paddle.to_tensor(labels)
    seg_t = paddle.to_tensor(seg)

    kstep = 1 if smoke else max(
        1, int(os.environ.get("BENCH_BERT_KSTEP", "1")))
    if kstep > 1:
        run = _kstep_runner(step, (ids_t._value, labels_t._value, seg_t._value), kstep)
    else:
        run = lambda: step(ids_t, labels_t, seg_t)  # noqa: E731

    for _ in range(warm):
        loss = run()
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = run()
    float(loss)
    dt = time.perf_counter() - t0

    tok_s = real_tokens * steps * kstep / dt
    n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
    flops_step = 6.0 * n_params * real_tokens + attn_flops
    mfu = (flops_step * steps * kstep / dt / _peak_flops()
           if not smoke else 0.0)
    return {"metric": "bert_large_mlm_train_packed",
            "tokens_per_sec": round(tok_s, 1),
            "step_ms": round(dt / (steps * kstep) * 1e3, 1),
            "mfu": round(mfu, 4), "steps_per_fence": kstep,
            "fill_rate": round(real_tokens / (B * S), 4),
            "params_m": round(n_params / 1e6, 1), "loss": float(loss)}


def bench_moe():
    jax, smoke = _setup()
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models.gpt_moe import GPTMoEConfig, GPTMoEForCausalLM

    if smoke:
        cfg = GPTMoEConfig(vocab_size=512, hidden_size=64,
                           num_hidden_layers=2, num_attention_heads=4,
                           intermediate_size=128,
                           max_position_embeddings=64, num_experts=4,
                           moe_topk=2)
        B, S, steps, warm = 2, 32, 2, 1
    else:
        cfg = GPTMoEConfig(vocab_size=50304, hidden_size=1024,
                           num_hidden_layers=8, num_attention_heads=16,
                           intermediate_size=4096,
                           max_position_embeddings=1024, num_experts=8,
                           moe_topk=2)
        B, S, steps, warm = 8, 1024, 10, 2

    paddle.seed(0)
    net = GPTMoEForCausalLM(cfg)                  # moe_group None: dense path
    skew = os.environ.get("BENCH_MOE_SKEW") == "1"
    if skew:
        # VERDICT r4 next-round #8: hot-expert stress — bias every gate so
        # ~90% of tokens route to experts 0/1; measures the active-MFU
        # degradation under capacity-drop pressure (tests/test_moe_skew.py
        # pins the correctness side)
        for name, p in net.named_parameters():
            if "gate" in name and p.ndim == 2 \
                    and p.shape[-1] == cfg.num_experts:
                v = np.asarray(p._value).copy()
                v[:, 0] += 4.0
                v[:, 1] += 3.5
                p.set_value(v)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=net.parameters())

    def loss_fn(model, ids, labels):
        return model.compute_loss(ids, labels)

    step = paddle.jit.TrainStep(net, loss_fn, opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32))
    labels = paddle.to_tensor(
        np.roll(np.asarray(ids._value), -1, axis=-1).astype(np.int64))

    kstep = 1 if smoke else max(
        1, int(os.environ.get("BENCH_MOE_KSTEP", "1")))
    if kstep > 1:
        run = _kstep_runner(step, (ids._value, labels._value), kstep)
    else:
        run = lambda: step(ids, labels)  # noqa: E731

    for _ in range(warm):
        loss = run()
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = run()
    float(loss)
    dt = time.perf_counter() - t0

    tok_s = B * S * steps * kstep / dt
    h, L = cfg.hidden_size, cfg.num_hidden_layers
    # ACTIVE flops/token: attention block 6·4h² + topk experts 6·2·h·ff
    # per layer + lm head + causal attention 6·L·S·h
    flops_tok = L * (6 * 4 * h * h
                     + cfg.moe_topk * 6 * 2 * h * cfg.intermediate_size) \
        + 6 * h * cfg.vocab_size + 6.0 * L * S * h
    mfu = flops_tok * tok_s / _peak_flops() if not smoke else 0.0
    n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
    return {"metric": "gpt_moe_train_dense" + ("_skew" if skew else ""),
            "tokens_per_sec": round(tok_s, 1),
            "step_ms": round(dt / (steps * kstep) * 1e3, 1),
            "active_mfu": round(mfu, 4), "steps_per_fence": kstep,
            "params_m": round(n_params / 1e6, 1), "loss": float(loss)}


def bench_encoder_int8():
    """A8W8 fused encoder inference vs the bf16 float stack (reference
    fused_multi_transformer_int8 path) at BERT-large geometry."""
    jax, smoke = _setup()
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn import (FusedMultiTransformer,
                                        FusedMultiTransformerInt8)

    if smoke:
        L, H, F, heads, B, S, iters = 2, 64, 128, 4, 2, 16, 2
    else:
        L, H, F, heads, B, S, iters = 12, 1024, 4096, 16, 8, 512, 20

    paddle.seed(0)
    m = FusedMultiTransformer(H, heads, F, num_layers=L)
    if not smoke:
        for _, p in m.named_parameters():
            p._value = p._value.astype(jnp.bfloat16)
    q = FusedMultiTransformerInt8.from_float(m)
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randn(B, S, H).astype(np.float32))
    if not smoke:
        x = x.astype("bfloat16")

    def timed(net):
        sf = paddle.jit.to_static(net.forward)     # one compiled program
        out = sf(x)
        float(out.astype("float32").sum())
        t0 = time.perf_counter()
        for _ in range(iters):
            out = sf(x)
        float(out.astype("float32").sum())
        return (time.perf_counter() - t0) / iters * 1e3

    t_float = timed(m)
    t_int8 = timed(q)
    ref = np.asarray(m(x).astype("float32")._value)
    got = np.asarray(q(x).astype("float32")._value)
    err = float(np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9))
    return {"metric": "fused_encoder_int8_vs_bf16",
            "bf16_ms": round(t_float, 2), "int8_ms": round(t_int8, 2),
            "speedup": round(t_float / t_int8, 2),
            "rel_err": round(err, 4),
            "geometry": f"L{L} h{H} ff{F} B{B} S{S}"}


def bench_decode_cb():
    """Serving throughput under CONTINUOUS BATCHING (VERDICT r4 item 4):
    stream 2x-slots ragged requests through the fixed-slot
    ContinuousBatchingEngine (paged KV, EOS-free + admit mid-decode).
    Aggregate tok/s counts ALL generated tokens over the full serve wall
    time — prefills and admission gaps included, the honest serving
    number."""
    jax, smoke = _setup()
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models import llama as L
    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig,
                                               _prefill_flags)

    if smoke:
        cfg = L.llama_tiny(num_hidden_layers=2)
        slots, n_req, lo, hi, new, chunk = 2, 4, 4, 12, 8, 4
        page, max_len = 4, 32
    else:
        cfg = L.LlamaConfig(
            vocab_size=32000, hidden_size=3072, intermediate_size=8192,
            num_hidden_layers=6, num_attention_heads=24,
            num_key_value_heads=24, max_position_embeddings=2048,
            dtype=jnp.bfloat16)
        slots, n_req, lo, hi, new, chunk = 16, 32, 300, 512, 128, 64
        page, max_len = 16, 640

    params = L.init_stacked_params(cfg, seed=0)
    int8_mode = os.environ.get("BENCH_DECODE_INT8") == "1"
    if int8_mode:
        from paddle_tpu.quantization import quantize_stacked_params
        params = quantize_stacked_params(params)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size,
                           (int(rng.randint(lo, hi + 1)),)).astype(np.int32)
               for _ in range(n_req)]

    def make_engine():
        return ContinuousBatchingEngine(
            cfg, GenerationConfig(max_new_tokens=new), num_slots=slots,
            page_size=page, max_seq_len=max_len, chunk=chunk)

    # warm: compile the step program on a small serve
    eng = make_engine()
    eng.serve(params, prompts[:slots])
    compiled_unified = eng._unified_step      # the (one) unified program

    eng = make_engine()
    eng._unified_step = compiled_unified
    # carry the host state the program baked in, or the fresh engine
    # treats the transplant as stale and recompiles (decoding._prefill_flags)
    eng._unified_flags = _prefill_flags()
    t0 = time.perf_counter()
    outs = eng.serve(params, prompts)
    dt = time.perf_counter() - t0
    total = sum(len(o) for o in outs)
    return {"metric": "llama_876M_serving_continuous_batching"
            + ("_int8" if int8_mode else ""),
            "slots": slots, "requests": n_req,
            "total_tokens": total,
            "agg_tokens_per_sec": round(total / dt, 1),
            "serve_s": round(dt, 2)}


def bench_vit():
    """Workload #5a: ViT-L/16 supervised training step (conv/attn mix).

    Default is the imperative-module TrainStep path — measured FASTER on
    chip (225.7 img/s) than the round-4 stacked lax.scan + dots-remat
    functional step (191.0 img/s; the scan needs remat to fit, and the
    recompute's extra HBM passes cost more than the per-tensor optimizer
    fusions it saves). BENCH_VIT_STACKED=1 runs the
    stacked path (parity-tested in test_vit)."""
    jax, smoke = _setup()
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.nn import functional as F
    from paddle_tpu.vision.models.vit import (
        vit_large_patch16_224, vit_tiny_test, stacked_params_from_module,
        build_vit_train_step)

    if smoke:
        B, side, steps, warm = 2, 16, 2, 1
    else:
        B = int(os.environ.get("BENCH_VIT_BATCH", "32"))
        side, steps, warm = 224, 10, 2

    paddle.seed(0)
    net = vit_tiny_test() if smoke else vit_large_patch16_224(class_num=1000)
    rng = np.random.RandomState(0)
    heads = 4 if smoke else 16
    patch = 4 if smoke else 16

    if os.environ.get("BENCH_VIT_STACKED") != "1":
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=net.parameters())
        if not smoke:
            amp.decorate(models=net, optimizers=opt, level="O2",
                         dtype="bfloat16")

        def loss_fn(model, x, y):
            return F.cross_entropy(model(x).astype("float32"), y)

        tstep = paddle.jit.TrainStep(net, loss_fn, opt)
        x = paddle.to_tensor(rng.randn(B, 3, side, side).astype(np.float32))
        if not smoke:
            x = x.astype("bfloat16")
        y = paddle.to_tensor(rng.randint(0, 10 if smoke else 1000,
                                         (B,)).astype(np.int64))
        ksteps = 1 if smoke else max(
            1, int(os.environ.get("BENCH_VIT_KSTEP", "6")))
        if ksteps > 1:
            # VERDICT r4 next-round #3: k steps per host fence — distinct
            # from the r4-rejected per-LAYER stacked scan. Sweep: k=6 is
            # the peak (241.8 img/s, 44.0%); k=8 measured a 19x
            # regression (XLA scheduling pathology, ViT-specific; BERT
            # runs k=8 fine) — keep k<=6.
            run = _kstep_runner(tstep, (x._value, y._value), ksteps)
        else:
            run = lambda: tstep(x, y)  # noqa: E731
    else:
        ksteps = 1  # stacked path: one step per dispatch
        params = stacked_params_from_module(net)
        dt_ = jnp.float32 if smoke else jnp.bfloat16
        if not smoke:
            params = {k: (v.astype(jnp.bfloat16)
                          if v.dtype == jnp.float32 and v.ndim > 1 else v)
                      for k, v in params.items()}
        sstep, init_opt = build_vit_train_step(
            num_heads=heads, patch=patch, learning_rate=1e-4, dtype=dt_)
        ostate = init_opt(params)
        xj = jnp.asarray(rng.randn(B, 3, side, side).astype(np.float32))
        yj = jnp.asarray(rng.randint(0, 10 if smoke else 1000, (B,)),
                         jnp.int32)
        state = {"p": params, "o": ostate}

        def run():
            loss, state["p"], state["o"] = sstep(state["p"], state["o"],
                                                 xj, yj)
            return loss

    # single source: the kstep computed where the runner was built (a
    # second env read here once drifted from the builder's default and
    # mis-scaled every reported metric by k)
    for _ in range(warm):
        loss = run()
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = run()
    float(loss)
    dt = time.perf_counter() - t0
    img_s = B * steps * ksteps / dt
    n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
    # ViT train flops/img ~= 6 * matmul params * tokens + attention
    tokens = (side // 16) ** 2 + 1
    flops_img = 6.0 * (n_params - 1000 * 1024) * tokens if not smoke else 0
    mfu = flops_img * img_s / _peak_flops() if not smoke else 0.0
    return {"metric": "vit_large_train", "img_per_sec": round(img_s, 1),
            "step_ms": round(dt / (steps * ksteps) * 1e3, 1),
            "mfu": round(mfu, 4), "steps_per_fence": ksteps,
            "params_m": round(n_params / 1e6, 1), "loss": float(loss)}


def bench_ppyoloe():
    """Workload #5b: PP-YOLOE-s detection training step."""
    jax, smoke = _setup()
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.vision.models.ppyoloe import PPYOLOE

    if smoke:
        B, side, steps, warm = 1, 64, 2, 1
        net = PPYOLOE(num_classes=4, width_mult=0.25, depth_mult=0.33)
    else:
        B, side, steps, warm = 16, 320, 10, 2
        net = PPYOLOE(num_classes=80, width_mult=0.5, depth_mult=0.33)

    paddle.seed(0)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=net.parameters())

    def loss_fn(model, x, gb, gl):
        return model.compute_loss(x, gb, gl)

    step = paddle.jit.TrainStep(net, loss_fn, opt)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(B, 3, side, side).astype(np.float32))
    G = 8
    gb = rng.rand(B, G, 4).astype(np.float32) * side
    gb[..., 2:] = np.maximum(gb[..., 2:], gb[..., :2] + 4)
    gl = rng.randint(0, 4 if smoke else 80, (B, G))
    gb_t = paddle.to_tensor(gb)
    gl_t = paddle.to_tensor(gl.astype(np.int32))
    for _ in range(warm):
        loss = step(x, gb_t, gl_t)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(x, gb_t, gl_t)
    float(loss)
    dt = time.perf_counter() - t0
    n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
    return {"metric": "ppyoloe_s_train", "img_per_sec": round(B * steps / dt, 1),
            "step_ms": round(dt / steps * 1e3, 1),
            "params_m": round(n_params / 1e6, 1), "loss": float(loss)}


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    benches = {"bert": bench_bert, "bert_packed": bench_bert_packed,
               "moe": bench_moe, "decode_cb": bench_decode_cb,
               "encoder_int8": bench_encoder_int8, "vit": bench_vit,
               "ppyoloe": bench_ppyoloe}
    if which != "all" and which not in benches:
        sys.exit(f"unknown bench {which!r}; pick from "
                 f"{['all'] + sorted(benches)}")
    for name, fn in benches.items():
        if which not in ("all", name):
            continue
        print(json.dumps(fn()))


if __name__ == "__main__":
    main()
