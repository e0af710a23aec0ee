"""Profile a workload train step on the chip (PROFILE_r3 methodology):
device-fenced wall clock + XLA cost analysis + jax.profiler trace with a
top-op table. Usage:  python benchmarks/profile_workload.py [bert|vit]

Writes benchmarks/PROFILE_<name>_r5.md and prints one JSON line.
"""

import glob
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import device_peaks


def _parse_trace(path):
    import gzip, json as _json, collections
    with gzip.open(path, "rt") as f:
        data = _json.load(f)
    events = data.get("traceEvents", [])
    pid_names = {e.get("pid"): str(e.get("args", {}).get("name", ""))
                 for e in events
                 if e.get("ph") == "M" and e.get("name") == "process_name"}
    device_pids = {p for p, n in pid_names.items()
                   if any(s in n.lower() for s in ("tpu", "device", "xla"))}
    agg = collections.Counter()
    cnt = collections.Counter()
    step_ms = 0.0
    for e in events:
        if e.get("ph") != "X":
            continue
        if device_pids and e.get("pid") not in device_pids:
            continue
        dur = float(e.get("dur", 0.0))
        name = str(e.get("name", "?"))
        if name.startswith("jit_"):
            step_ms = max(step_ms, dur / 1e3)
            continue
        if name.isdigit():
            continue
        # group fusion.1234 -> fusion, cluster repeated per-layer ops
        base = name.split(".")[0]
        agg[base] += dur
        cnt[base] += 1
    top = [(f"{n} x{cnt[n]}", d / 1e3) for n, d in agg.most_common(25)]
    total = sum(agg.values()) / 1e3
    top.append(("TOTAL-device-op-time", total))
    return top, step_ms

def _build_bert(jax, smoke):
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.models.ernie import ErnieConfig, ErnieForMaskedLM

    if smoke:
        cfg = ErnieConfig(vocab_size=512, hidden_size=64,
                          num_hidden_layers=2, num_attention_heads=4,
                          intermediate_size=128, max_position_embeddings=64)
        B, S = 2, 32
    else:
        cfg = ErnieConfig(vocab_size=30522, hidden_size=1024,
                          num_hidden_layers=24, num_attention_heads=16,
                          intermediate_size=4096,
                          max_position_embeddings=512)
        B, S = 16, 512
    paddle.seed(0)
    net = ErnieForMaskedLM(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=net.parameters())
    if not smoke:
        amp.decorate(models=net, optimizers=opt, level="O2", dtype="bfloat16")
    step = paddle.jit.TrainStep(net, lambda m, i, l: m.compute_loss(i, l), opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32))
    labels = rng.randint(0, cfg.vocab_size, (B, S))
    labels[rng.rand(B, S) > 0.15] = -100
    labels = paddle.to_tensor(labels.astype(np.int64))

    n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
    flops_tok = (6.0 * n_params
                 + 12.0 * cfg.num_hidden_layers * S * cfg.hidden_size)
    return (lambda: step(ids, labels)), B * S, flops_tok, \
        f"BERT-large MLM (h=1024 L=24 S={S} B={B}, bf16 O2)"


def _build_vit(jax, smoke):
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.nn import functional as F
    from paddle_tpu.vision.models.vit import (vit_large_patch16_224,
                                              vit_tiny_test)

    B, side = (2, 16) if smoke else (32, 224)
    paddle.seed(0)
    net = vit_tiny_test() if smoke else vit_large_patch16_224(class_num=1000)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=net.parameters())
    if not smoke:
        amp.decorate(models=net, optimizers=opt, level="O2", dtype="bfloat16")

    def loss_fn(model, x, y):
        return F.cross_entropy(model(x).astype("float32"), y)

    step = paddle.jit.TrainStep(net, loss_fn, opt)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(B, 3, side, side).astype(np.float32))
    if not smoke:
        x = x.astype("bfloat16")
    y = paddle.to_tensor(rng.randint(0, 10 if smoke else 1000,
                                     (B,)).astype(np.int64))
    n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
    tokens = (side // 16) ** 2 + 1
    # same flops/img formula as bench_workloads.bench_vit
    flops_img = 6.0 * (n_params - 1000 * 1024) * tokens if not smoke else 1.0
    return (lambda: step(x, y)), B, flops_img, \
        f"ViT-L/16 train (B={B}, {side}^2, bf16 O2)"


def _build_bert_packed(jax, smoke):
    """The PACKED encoder step (VERDICT r4 next-round #7): same packing,
    segment-masked flash and real-token accounting as
    bench_workloads.bench_bert_packed."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.models.ernie import ErnieConfig, ErnieForMaskedLM

    if smoke:
        cfg = ErnieConfig(vocab_size=512, hidden_size=64,
                          num_hidden_layers=2, num_attention_heads=4,
                          intermediate_size=128, max_position_embeddings=64)
        B, S, lo, hi = 2, 32, 8, 32
    else:
        cfg = ErnieConfig(vocab_size=30522, hidden_size=1024,
                          num_hidden_layers=24, num_attention_heads=16,
                          intermediate_size=4096,
                          max_position_embeddings=512)
        B, S, lo, hi = 16, 512, 64, 512
    paddle.seed(0)
    net = ErnieForMaskedLM(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=net.parameters())
    if not smoke:
        amp.decorate(models=net, optimizers=opt, level="O2",
                     dtype="bfloat16")
    step = paddle.jit.TrainStep(
        net, lambda m, i, l, s: m.compute_loss(i, l, segment_ids=s), opt)

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
            mask = rng.rand(n) < 0.15
            labels[r, at:at + n] = np.where(mask, tok, -100)
            at += n
    real_tokens = int((seg >= 0).sum())
    attn_flops = 12.0 * cfg.num_hidden_layers * cfg.hidden_size * float(
        sum(n * n for r in seg_lens for n in r))
    ids_t = paddle.to_tensor(ids)
    labels_t = paddle.to_tensor(labels)
    seg_t = paddle.to_tensor(seg)
    n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
    # report per-REAL-token flops so the harness's MFU matches the bench
    flops_tok = 6.0 * n_params + attn_flops / max(real_tokens, 1)
    return (lambda: step(ids_t, labels_t, seg_t)), real_tokens, flops_tok, \
        (f"BERT-large MLM PACKED (h=1024 L=24 S={S} B={B}, "
         f"fill={real_tokens / (B * S):.3f}, bf16 O2)")


BUILDERS = {"bert": _build_bert, "vit": _build_vit,
            "bert_packed": _build_bert_packed}


def main():
    import jax

    name = sys.argv[1] if len(sys.argv) > 1 else "bert"
    from paddle_tpu.ops._common import is_tpu_platform

    smoke = not is_tpu_platform(jax.devices()[0].platform)
    run, units_per_step, flops_unit, desc = BUILDERS[name](jax, smoke)

    loss = run()
    float(loss)
    steps = 2 if smoke else 6
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = run()
    float(loss)
    step_s = (time.perf_counter() - t0) / steps

    trace_dir = f"/tmp/{name}_trace_r5"
    top_ops, device_step_ms = [], None
    try:
        with jax.profiler.trace(trace_dir):
            loss = run()
            float(loss)
        tf = sorted(glob.glob(trace_dir + "/**/*.trace.json.gz",
                              recursive=True), key=os.path.getmtime)
        if tf:
            top_ops, device_step_ms = _parse_trace(tf[-1])
            if device_step_ms:
                step_s = device_step_ms / 1e3
    except Exception as e:
        top_ops = [(f"trace failed: {type(e).__name__}: {e}", 0.0)]

    kind = jax.devices()[0].device_kind
    mfu = (flops_unit * units_per_step / step_s
           / device_peaks(kind)["bf16_flops"]) if not smoke else 0.0
    lines = [
        f"# {name} step profile — round 5",
        "",
        f"Config: {desc}, single {kind} chip.",
        "",
        f"- device step time: **{step_s * 1e3:.1f} ms** "
        f"({units_per_step / step_s:,.0f} units/s)",
        f"- **MFU {mfu * 100:.1f}%**",
        "",
        "## Top device ops by INCLUSIVE time (one traced step)",
        "",
        "| op | total ms |",
        "|---|---|",
    ]
    for n, ms in top_ops:
        lines.append(f"| {n[:90]} | {ms:.1f} |")
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       f"PROFILE_{name}_r5.md")
    with open(out, "w") as f:
        f.write("\n".join(lines) + "\n")
    print(json.dumps({"workload": name, "step_ms": round(step_s * 1e3, 1),
                      "mfu": round(mfu, 4), "summary": out}))


if __name__ == "__main__":
    main()
