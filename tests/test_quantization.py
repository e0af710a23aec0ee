"""Weight-only quantization: int8/int4 roundtrip accuracy, linear parity,
layer conversion (SURVEY.md §2.2 int8 serving path)."""

import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.quantization import (
    WeightOnlyLinear, quantize_stacked_params, weight_dequantize,
    weight_only_linear, weight_quantize,
)

pytestmark = pytest.mark.slow  # core tier: -m 'not slow'


def test_int8_roundtrip_error():
    rng = np.random.RandomState(0)
    w = rng.randn(64, 32).astype(np.float32)
    q, s = weight_quantize(w)
    assert q.dtype == jnp.int8 and s.shape == (32,)
    wd = np.asarray(weight_dequantize(q, s))
    rel = np.abs(wd - w).max() / np.abs(w).max()
    assert rel < 0.01  # 127-level symmetric quant: <1% of max


def test_int4_roundtrip_error():
    rng = np.random.RandomState(1)
    w = rng.randn(64, 16).astype(np.float32)
    q, s = weight_quantize(w, "weight_only_int4")
    assert q.shape == (32, 16)  # packed two per byte
    wd = np.asarray(weight_dequantize(q, s, "weight_only_int4"))
    rel = np.abs(wd - w).max() / np.abs(w).max()
    assert rel < 0.12  # 15-level quant


def test_weight_only_linear_matches_dense():
    rng = np.random.RandomState(2)
    x = paddle.to_tensor(rng.randn(4, 64).astype(np.float32))
    w = rng.randn(64, 32).astype(np.float32)
    b = rng.randn(32).astype(np.float32)
    q, s = weight_quantize(w)
    y = weight_only_linear(x, paddle.to_tensor(np.asarray(q)),
                           paddle.to_tensor(np.asarray(s)),
                           paddle.to_tensor(b))
    ref = np.asarray(x._value) @ w + b
    rel = np.abs(np.asarray(y._value) - ref).max() / np.abs(ref).max()
    assert rel < 0.02, rel


def test_from_linear_conversion():
    paddle.seed(3)
    lin = nn.Linear(64, 32)
    qlin = WeightOnlyLinear.from_linear(lin)
    x = paddle.to_tensor(np.random.RandomState(3)
                         .randn(2, 64).astype(np.float32))
    ref = np.asarray(lin(x)._value)
    out = np.asarray(qlin(x)._value)
    rel = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9)
    assert rel < 0.02, rel
    # quantized weight is not trainable
    assert qlin.weight.stop_gradient


def test_quantize_stacked_params():
    from paddle_tpu.models import llama as L
    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=0)
    qp = quantize_stacked_params(params)
    assert qp["wq"]["q"].dtype == jnp.int8
    assert qp["wq"]["q"].shape == params["wq"].shape
    assert qp["wq"]["scale"].shape == params["wq"].shape[:1] + \
        params["wq"].shape[2:]
    # embed/norms untouched
    assert qp["embed"] is params["embed"]
    # dequant error small
    wd = np.asarray(weight_dequantize(qp["wq"]["q"][0], qp["wq"]["scale"][0]))
    ref = np.asarray(params["wq"][0], dtype=np.float32)
    assert np.abs(wd - ref).max() / np.abs(ref).max() < 0.01


def test_quantized_params_drive_generation():
    """The serving path consumes the {"q","scale"} format directly: greedy
    generation from int8-stored weights matches the fp32 oracle's (weight
    error <1%)."""
    from paddle_tpu.models import llama as L
    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig)
    from _oracle import greedy_reforward
    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=4)
    qp = quantize_stacked_params(params)
    prompt = np.array([3, 1, 4, 1, 5], np.int32)
    t_fp = np.asarray(greedy_reforward(params, cfg, prompt, 6))
    eng = ContinuousBatchingEngine(cfg, GenerationConfig(max_new_tokens=6),
                                   num_slots=1, page_size=4, max_seq_len=16)
    t_q = np.asarray(eng.serve(qp, [prompt])[0])
    assert (t_fp == t_q).mean() >= 0.5, (t_fp, t_q)


def test_unknown_weight_dtype_raises():
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    q = paddle.to_tensor(np.zeros((4, 4), np.int8))
    s = paddle.to_tensor(np.ones(4, np.float32))
    with pytest.raises(ValueError, match="weight_dtype"):
        weight_only_linear(x, q, s, weight_dtype="bf16")
    with pytest.raises(ValueError, match="even in_features"):
        WeightOnlyLinear(65, 8, weight_dtype="int4")


def test_from_linear_accepts_long_alias():
    paddle.seed(6)
    lin = nn.Linear(16, 8)
    q = WeightOnlyLinear.from_linear(lin, weight_dtype="weight_only_int8")
    x = paddle.to_tensor(np.ones((2, 16), np.float32))
    ref = np.asarray(lin(x)._value)
    out = np.asarray(q(x)._value)
    assert np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9) < 0.02


def test_stacked_scale_dequant_broadcast():
    from paddle_tpu.models import llama as L
    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=0)
    qp = quantize_stacked_params(params)
    # stacked (L, in, out) with (L, out) scales dequantizes in one call
    wd = np.asarray(weight_dequantize(qp["wq"]["q"], qp["wq"]["scale"]))
    assert wd.shape == params["wq"].shape


def test_int4_stacked_dequant_matches_per_layer():
    # ADVICE round-1: int4 unpack must interleave along the INPUT axis so a
    # stacked (L, in/2, out) buffer dequantizes layerwise-identically.
    rng = np.random.RandomState(7)
    ws = [rng.randn(8, 6).astype(np.float32) for _ in range(3)]
    qs, ss = zip(*(weight_quantize(paddle.to_tensor(w), "weight_only_int4")
                   for w in ws))
    import jax.numpy as jnp
    qst = jnp.stack([q._value if hasattr(q, "_value") else q for q in qs])
    sst = jnp.stack([s._value if hasattr(s, "_value") else s for s in ss])
    stacked = np.asarray(weight_dequantize(qst, sst, "weight_only_int4"))
    for i, (q, s) in enumerate(zip(qs, ss)):
        one = np.asarray(weight_dequantize(q._value if hasattr(q, "_value")
                                           else q,
                                           s._value if hasattr(s, "_value")
                                           else s, "weight_only_int4"))
        np.testing.assert_allclose(stacked[i], one, rtol=1e-6)
        assert one.shape == (8, 6)


class TestFusedMultiTransformerInt8:
    """A8W8 fused encoder (reference fused_multi_transformer_int8_op.cu:§0):
    int8 weights + quantized activations must track the float stack."""

    def _float_stack(self, L=2, H=32, F=64, heads=4):
        paddle.seed(0)
        from paddle_tpu.incubate.nn import FusedMultiTransformer
        m = FusedMultiTransformer(H, heads, F, num_layers=L)
        # give the projections non-trivial weights
        rs = np.random.RandomState(0)
        for plist in (m.qkv_weights, m.linear_weights, m.ffn1_weights,
                      m.ffn2_weights):
            for p in plist:
                p._value = jnp.asarray(
                    rs.randn(*p.shape) * 0.05, jnp.float32)
        return m

    def test_prefill_tracks_float_stack(self):
        from paddle_tpu.incubate.nn import FusedMultiTransformerInt8
        m = self._float_stack()
        q = FusedMultiTransformerInt8.from_float(m)
        rs = np.random.RandomState(1)
        x = paddle.to_tensor(rs.randn(2, 8, 32).astype(np.float32))
        ref = np.asarray(m(x)._value)
        got = np.asarray(q(x)._value)
        # int8 quantization error: ~1% relative of the activation scale
        err = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)
        assert err < 0.05, err
        # and the outputs are NOT identical (the int8 path really ran)
        assert not np.allclose(got, ref, rtol=1e-6, atol=1e-7)

    def test_decode_path_consistent_with_prefill(self):
        from paddle_tpu.incubate.nn import FusedMultiTransformerInt8
        m = self._float_stack()
        q = FusedMultiTransformerInt8.from_float(m)
        rs = np.random.RandomState(2)
        S = 6
        x = paddle.to_tensor(rs.randn(1, S, 32).astype(np.float32))
        full = np.asarray(q(x)._value)
        # prefill S-1 tokens with a cache, then decode token S-1
        out, cache = q(paddle.to_tensor(np.asarray(x._value)[:, :S - 1]),
                       gen_cache_len=S)
        step, _ = q(paddle.to_tensor(np.asarray(x._value)[:, S - 1:]),
                    caches=cache, time_step=S - 1)
        np.testing.assert_allclose(np.asarray(step._value)[:, 0],
                                   full[:, -1], rtol=2e-2, atol=2e-2)

    def test_calibrated_in_scales_used(self):
        from paddle_tpu.incubate.nn import FusedMultiTransformerInt8
        m = self._float_stack(L=1)
        # absurdly small calibrated scale clips activations -> output departs
        q_dyn = FusedMultiTransformerInt8.from_float(m)
        q_cal = FusedMultiTransformerInt8.from_float(
            m, qkv_in_scale=[1e-6], linear_in_scale=[1e-6],
            ffn1_in_scale=[1e-6], ffn2_in_scale=[1e-6])
        rs = np.random.RandomState(3)
        x = paddle.to_tensor(rs.randn(2, 4, 32).astype(np.float32))
        a = np.asarray(q_dyn(x)._value)
        b = np.asarray(q_cal(x)._value)
        assert not np.allclose(a, b, rtol=1e-3, atol=1e-3)

    def test_calibrated_scale_convention_matches_dynamic(self):
        """Reference convention (ADVICE r3 #1): in_scale is the max-abs
        RANGE, q = round(127*x/in_scale). A calibrated scale equal to the
        observed activation amax must reproduce the dynamic-amax path."""
        from paddle_tpu.ops.fused_transformer_block import _int8_mm
        rs = np.random.RandomState(4)
        x = jnp.asarray(rs.randn(1, 32).astype(np.float32))
        wq = jnp.asarray(rs.randint(-127, 128, (32, 16)), jnp.int8)
        ws = jnp.asarray(np.abs(rs.randn(16)).astype(np.float32) * 0.01)
        amax = float(jnp.max(jnp.abs(x)))
        dyn = np.asarray(_int8_mm(x, wq, ws))
        cal = np.asarray(_int8_mm(x, wq, ws, in_scale=amax))
        np.testing.assert_allclose(cal, dyn, rtol=1e-6, atol=1e-6)


class TestQATWorkflow:
    """Round-5 QAT/PTQ surface (reference python/paddle/quantization/)."""

    def _net(self):
        paddle.seed(0)
        return paddle.nn.Sequential(
            paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
            paddle.nn.Linear(16, 4))

    def test_quantize_swaps_configured_linears(self):
        from paddle_tpu.quantization import (FakeQuanterWithAbsMax, QAT,
                                             QuantConfig, quanted_layers)
        net = self._net()
        QAT(QuantConfig(activation=FakeQuanterWithAbsMax)).quantize(net)
        assert len(quanted_layers(net)) == 2

    def test_fake_quant_close_to_float_and_ste_trains(self):
        from paddle_tpu import optimizer
        from paddle_tpu.quantization import (FakeQuanterWithAbsMax, QAT,
                                             QuantConfig)
        net = self._net()
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randn(32, 8).astype(np.float32))
        ref = np.asarray(net(x)._value)
        QAT(QuantConfig(activation=FakeQuanterWithAbsMax)).quantize(net)
        for _ in range(5):
            out = net(x)          # calibrates the moving-average scales
        err = np.abs(np.asarray(out._value) - ref).max() \
            / (np.abs(ref).max() + 1e-9)
        assert err < 0.05
        # straight-through gradients train under the compiled TrainStep
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=net.parameters())
        y = paddle.to_tensor(rs.randn(32, 4).astype(np.float32))
        step = paddle.jit.TrainStep(
            net, lambda m, a, b: ((m(a) - b) ** 2).mean(), opt)
        l0 = float(step(x, y)._value)
        for _ in range(25):
            l1 = float(step(x, y)._value)
        assert l1 < l0

    def test_convert_lowers_to_weight_only(self):
        from paddle_tpu.quantization import (FakeQuanterWithAbsMax, QAT,
                                             QuantConfig, WeightOnlyLinear)
        net = self._net()
        rs = np.random.RandomState(1)
        x = paddle.to_tensor(rs.randn(8, 8).astype(np.float32))
        q = QAT(QuantConfig(activation=FakeQuanterWithAbsMax))
        q.quantize(net)
        fq = np.asarray(net(x)._value)
        q.convert(net)
        kinds = [type(s).__name__ for _, s in net.named_sublayers()]
        assert kinds.count("WeightOnlyLinear") == 2
        out = np.asarray(net(x)._value)
        # int8-weight output stays close to the fake-quant one (acts no
        # longer quantized; weight grid identical)
        assert np.abs(out - fq).max() / (np.abs(fq).max() + 1e-9) < 0.05

    def test_ptq_observer_flow(self):
        from paddle_tpu.quantization import PTQ, AbsmaxObserver
        net = paddle.nn.Sequential(paddle.nn.Linear(8, 8),
                                   paddle.nn.Linear(8, 2))
        p = PTQ()
        p.quantize(net)
        rs = np.random.RandomState(2)
        x = paddle.to_tensor(rs.randn(16, 8).astype(np.float32))
        ref = np.asarray(net(x)._value)
        for _ in range(3):
            net(x)
        # observers collected a positive scale
        obs = [s for _, s in net.named_sublayers()
               if isinstance(s, AbsmaxObserver)]
        assert obs and all(o.scale > 0 for o in obs)
        p.convert(net)
        out = np.asarray(net(x)._value)
        assert np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9) < 0.05

    def test_name_and_type_config(self):
        from paddle_tpu.quantization import (FakeQuanterWithAbsMax, QAT,
                                             QuantConfig, quanted_layers)
        net = self._net()
        cfg = QuantConfig().add_name_config(
            "0", activation=FakeQuanterWithAbsMax)
        QAT(cfg).quantize(net)
        assert [n for n, _ in quanted_layers(net)] == ["0"]

    def test_cold_start_compiled_qat_calibrates(self):
        """Review r5: a QAT net whose FIRST forwards run under the
        compiled step must still calibrate (scale buffer rides the bind
        carry like BN stats) instead of collapsing activations to 0."""
        from paddle_tpu import optimizer
        from paddle_tpu.quantization import (FakeQuanterWithAbsMax, QAT,
                                             QuantConfig, quanted_layers)
        net = self._net()
        QAT(QuantConfig(activation=FakeQuanterWithAbsMax)).quantize(net)
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=net.parameters())
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randn(32, 8).astype(np.float32))
        y = paddle.to_tensor(rs.randn(32, 4).astype(np.float32))
        step = paddle.jit.TrainStep(
            net, lambda m, a, b: ((m(a) - b) ** 2).mean(), opt)
        l0 = float(step(x, y)._value)
        for _ in range(20):
            l1 = float(step(x, y)._value)
        # scales calibrated through the compiled path (were frozen 0,
        # which collapsed every activation to ~0 and froze the loss at
        # the predict-zeros MSE)
        for _, ql in quanted_layers(net):
            assert float(ql.activation_quanter.scale._value) > 0.0
        # and training makes progress (the broken path could not)
        assert l1 < l0

    def test_weight_bits_config_respected(self):
        from paddle_tpu.quantization import (FakeQuanterWithAbsMax, QAT,
                                             QuantConfig, quanted_layers)
        net = self._net()
        cfg = QuantConfig(activation=FakeQuanterWithAbsMax, weight=4)
        QAT(cfg).quantize(net)
        assert all(q.weight_bits == 4 for _, q in quanted_layers(net))
        with pytest.raises(ValueError, match="weight quanter"):
            QuantConfig(weight="int8")

    def test_ptq_scales_reach_converted_layers(self):
        from paddle_tpu.quantization import PTQ
        net = paddle.nn.Sequential(paddle.nn.Linear(8, 8),
                                   paddle.nn.Linear(8, 2))
        p = PTQ()
        p.quantize(net)
        rs = np.random.RandomState(2)
        x = paddle.to_tensor(rs.randn(16, 8).astype(np.float32))
        for _ in range(3):
            net(x)
        scales = p.activation_scales(net)
        assert scales and all(v > 0 for v in scales.values())
        p.convert(net)
        for _, sub in net.named_sublayers():
            if type(sub).__name__ == "WeightOnlyLinear":
                assert getattr(sub, "act_scale", 0) > 0
