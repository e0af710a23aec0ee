"""ResNet-50 single-chip training throughput (workload #1, SURVEY §7 M1
gate). Synthetic ImageNet shapes through the compiled TrainStep.

Run on the real chip: python benchmarks/bench_resnet.py
CPU smoke: JAX_PLATFORMS=cpu BENCH_RESNET_SMOKE=1 python ...
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp


    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.nn import functional as F
    from paddle_tpu.ops._common import is_tpu_platform
    from paddle_tpu.vision.models import resnet50, resnet18

    platform = jax.devices()[0].platform
    smoke = os.environ.get("BENCH_RESNET_SMOKE") == "1" or \
        not is_tpu_platform(platform)
    if smoke:
        B, side, steps, model_fn, name = 8, 64, 3, resnet18, "resnet18-smoke"
    else:
        B, side, steps, model_fn, name = 128, 224, 20, resnet50, "resnet50"

    paddle.seed(0)
    net = model_fn(num_classes=1000)
    if not smoke:
        # bf16 compute, fp32 master weights (the TPU training recipe)
        from paddle_tpu import amp
        opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                 parameters=net.parameters())
        amp.decorate(models=net, optimizers=opt, level="O2",
                     dtype="bfloat16")
    else:
        opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                 parameters=net.parameters())

    def loss_fn(model, x, y):
        return F.cross_entropy(model(x).astype("float32"), y)

    step = paddle.jit.TrainStep(net, loss_fn, opt)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(B, 3, side, side).astype(np.float32))
    if not smoke:
        x = x.astype("bfloat16")
    y = paddle.to_tensor(rng.randint(0, 1000, (B,)).astype(np.int64))

    loss = step(x, y)
    float(loss._value)  # fence
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(x, y)
    float(loss._value)
    dt = time.perf_counter() - t0
    img_s = B * steps / dt
    print(f"{name} platform={platform} batch={B} {img_s:.1f} img/s "
          f"({dt / steps * 1e3:.1f} ms/step, loss={float(loss._value):.3f})")


if __name__ == "__main__":
    main()
