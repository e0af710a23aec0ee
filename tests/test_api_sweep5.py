"""Round-5 API-audit sweep #5: paddle.audio, paddle.text (Viterbi),
paddle.autograd.jacobian + incubate.autograd functional transforms,
paddle.utils (dlpack, unique_name), paddle.onnx shim.

Reference: python/paddle/{audio,text,autograd,utils,onnx}/:§0.
"""

import itertools
import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle


class TestAudioFunctional:
    def test_hz_mel_roundtrip(self):
        from paddle_tpu.audio import functional as AF
        for htk in (False, True):
            for f in (60.0, 440.0, 1000.0, 4000.0):
                back = AF.mel_to_hz(AF.hz_to_mel(f, htk=htk), htk=htk)
                assert abs(back - f) < 1e-2 * max(1.0, f / 100)

    def test_htk_formula(self):
        from paddle_tpu.audio import functional as AF
        f = 700.0
        want = 2595.0 * math.log10(2.0)
        assert abs(AF.hz_to_mel(f, htk=True) - want) < 1e-3

    def test_fbank_matrix_properties(self):
        from paddle_tpu.audio import functional as AF
        fb = np.asarray(AF.compute_fbank_matrix(
            16000, 512, n_mels=40)._value)
        assert fb.shape == (40, 257)
        assert (fb >= 0).all()
        # each filter is unimodal triangular: nonzero support is contiguous
        for row in fb:
            nz = np.nonzero(row)[0]
            if len(nz):
                assert (np.diff(nz) == 1).all()

    def test_power_to_db(self):
        from paddle_tpu.audio import functional as AF
        x = paddle.to_tensor(np.array([1.0, 10.0, 100.0], np.float32))
        db = np.asarray(AF.power_to_db(x, top_db=None)._value)
        np.testing.assert_allclose(db, [0.0, 10.0, 20.0], atol=1e-4)
        db2 = np.asarray(AF.power_to_db(x, top_db=15.0)._value)
        np.testing.assert_allclose(db2, [5.0, 10.0, 20.0], atol=1e-4)

    def test_create_dct_ortho(self):
        from paddle_tpu.audio import functional as AF
        d = np.asarray(AF.create_dct(8, 8)._value)
        # orthonormal: D^T D = I for the square case
        np.testing.assert_allclose(d.T @ d, np.eye(8), atol=1e-5)

    def test_windows(self):
        from paddle_tpu.audio import functional as AF
        for name in ("hann", "hamming", "blackman", "bartlett",
                     ("kaiser", 8.0), ("gaussian", 3.0),
                     ("exponential", None, 2.0), "triang", "bohman"):
            w = np.asarray(AF.get_window(name, 32)._value)
            assert w.shape == (32,) and np.isfinite(w).all()
        # periodic hann of even length: w[k] = sin^2(pi k / N)
        w = np.asarray(AF.get_window("hann", 8)._value)
        k = np.arange(8)
        np.testing.assert_allclose(w, np.sin(np.pi * k / 8) ** 2, atol=1e-6)


class TestAudioFeatures:
    def test_shapes_and_jit(self):
        from paddle_tpu.audio.features import (MFCC, LogMelSpectrogram,
                                               MelSpectrogram, Spectrogram)
        x = paddle.to_tensor(
            np.sin(np.arange(4000) * 0.05).astype(np.float32)[None])
        spec = Spectrogram(n_fft=256)
        assert tuple(spec(x).shape) == (1, 129, 63)
        mel = MelSpectrogram(sr=8000, n_fft=256, n_mels=32)
        assert tuple(mel(x).shape) == (1, 32, 63)
        logmel = LogMelSpectrogram(sr=8000, n_fft=256, n_mels=32)
        assert tuple(logmel(x).shape) == (1, 32, 63)
        mfcc = MFCC(sr=8000, n_mfcc=13, n_fft=256, n_mels=32)
        assert tuple(mfcc(x).shape) == (1, 13, 63)

        # the whole pipeline traces under jit
        f = jax.jit(lambda v: mfcc(paddle.to_tensor(v))._value)
        np.testing.assert_allclose(np.asarray(f(x._value)),
                                   np.asarray(mfcc(x)._value),
                                   rtol=2e-4, atol=2e-4)

    def test_mel_matches_manual_pipeline(self):
        from paddle_tpu.audio import functional as AF
        from paddle_tpu.audio.features import MelSpectrogram
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randn(1, 2000).astype(np.float32))
        mel = MelSpectrogram(sr=8000, n_fft=256, n_mels=20, power=2.0)
        got = np.asarray(mel(x)._value)
        spec = paddle.signal.stft(
            x, 256, hop_length=64, window=AF.get_window("hann", 256))
        pow_spec = np.abs(np.asarray(spec._value)) ** 2
        fb = np.asarray(AF.compute_fbank_matrix(
            8000, 256, n_mels=20, f_min=50.0)._value)
        want = fb @ pow_spec[0]
        np.testing.assert_allclose(got[0], want, rtol=1e-3, atol=1e-3)


class TestAudioBackends:
    def test_wav_roundtrip(self, tmp_path):
        from paddle_tpu.audio import backends
        x = paddle.to_tensor(
            (0.5 * np.sin(np.arange(800) * 0.1)).astype(np.float32)[None])
        p = str(tmp_path / "t.wav")
        backends.save(p, x, 8000)
        w, sr = backends.load(p)
        assert sr == 8000 and tuple(w.shape) == (1, 800)
        np.testing.assert_allclose(np.asarray(w._value),
                                   np.asarray(x._value), atol=1e-4)
        meta = backends.info(p)
        assert meta.sample_rate == 8000 and meta.num_frames == 800
        assert meta.bits_per_sample == 16


class TestViterbi:
    def _brute(self, emis, trans, L, bos_eos):
        C = trans.shape[0]
        best, bp = -1e18, None
        for seq in itertools.product(range(C), repeat=int(L)):
            s = emis[0, seq[0]] + (trans[C - 2, seq[0]] if bos_eos else 0.0)
            for t in range(1, L):
                s += trans[seq[t - 1], seq[t]] + emis[t, seq[t]]
            if bos_eos:
                s += trans[seq[-1], C - 1]
            if s > best:
                best, bp = s, seq
        return best, list(bp)

    @pytest.mark.parametrize("bos_eos", [True, False])
    def test_matches_brute_force(self, bos_eos):
        from paddle_tpu.text import viterbi_decode
        rs = np.random.RandomState(1)
        B, T, C = 3, 5, 4
        emis = rs.randn(B, T, C).astype(np.float32)
        trans = rs.randn(C, C).astype(np.float32)
        lens = np.array([5, 3, 1], np.int32)
        scores, paths = viterbi_decode(
            paddle.to_tensor(emis), paddle.to_tensor(trans),
            paddle.to_tensor(lens), include_bos_eos_tag=bos_eos)
        for b in range(B):
            want_s, want_p = self._brute(emis[b], trans, lens[b], bos_eos)
            assert abs(float(np.asarray(scores._value)[b]) - want_s) < 1e-4
            got_p = list(np.asarray(paths._value)[b][:lens[b]])
            assert got_p == want_p
            # padding zeroed
            assert (np.asarray(paths._value)[b][lens[b]:] == 0).all()

    def test_layer_form_and_jit(self):
        from paddle_tpu.text import ViterbiDecoder
        rs = np.random.RandomState(2)
        emis = rs.randn(2, 4, 5).astype(np.float32)
        trans = rs.randn(5, 5).astype(np.float32)
        lens = np.array([4, 4], np.int32)
        dec = ViterbiDecoder(paddle.to_tensor(trans))
        s1, p1 = dec(paddle.to_tensor(emis), paddle.to_tensor(lens))

        f = jax.jit(lambda e, t, n: tuple(
            o._value for o in dec(paddle.to_tensor(e), paddle.to_tensor(n))))
        s2, p2 = f(emis, trans, lens)
        np.testing.assert_allclose(np.asarray(s1._value), np.asarray(s2),
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(p1._value), np.asarray(p2))


class TestAutogradJacobian:
    def test_basic(self):
        from paddle_tpu.autograd import jacobian
        x = paddle.to_tensor(np.array([1.0, 2.0, 3.0], np.float32),
                             stop_gradient=False)
        y = x * x
        J = np.asarray(jacobian(y, x)._value)
        np.testing.assert_allclose(J, np.diag([2.0, 4.0, 6.0]), atol=1e-5)

    def test_nondiag_and_multi_xs(self):
        from paddle_tpu.autograd import jacobian
        a = paddle.to_tensor(np.array([1.0, 2.0], np.float32),
                             stop_gradient=False)
        b = paddle.to_tensor(np.array([3.0], np.float32),
                             stop_gradient=False)
        y = paddle.concat([a.sum().reshape([1]) * b, a * 2.0])
        Ja, Jb = jacobian(y, [a, b])
        np.testing.assert_allclose(np.asarray(Ja._value),
                                   [[3.0, 3.0], [2.0, 0.0], [0.0, 2.0]],
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(Jb._value),
                                   [[3.0], [0.0], [0.0]], atol=1e-5)

    def test_batch_axis(self):
        from paddle_tpu.autograd import jacobian
        rs = np.random.RandomState(0)
        W = rs.randn(3, 2).astype(np.float32)
        x = paddle.to_tensor(rs.randn(4, 3).astype(np.float32),
                             stop_gradient=False)
        y = paddle.matmul(x, paddle.to_tensor(W))
        J = np.asarray(jacobian(y, x, batch_axis=0)._value)
        assert J.shape == (4, 2, 3)
        for bidx in range(4):
            np.testing.assert_allclose(J[bidx], W.T, atol=1e-5)

    def test_hessian_raises_with_pointer(self):
        from paddle_tpu.autograd import hessian
        x = paddle.to_tensor(np.ones(2, np.float32), stop_gradient=False)
        y = (x * x).sum()
        with pytest.raises(NotImplementedError, match="incubate"):
            hessian(y, x)


class TestIncubateAutograd:
    def test_jvp_vjp(self):
        from paddle_tpu.incubate.autograd import jvp, vjp
        x = paddle.to_tensor(np.array([1.0, 2.0], np.float32))
        out, tan = jvp(lambda v: v * v, x)
        np.testing.assert_allclose(np.asarray(tan._value), [2.0, 4.0])
        out, g = vjp(lambda v: (v ** 3).sum(), x)
        np.testing.assert_allclose(np.asarray(g._value), [3.0, 12.0])

    def test_jacobian_hessian(self):
        from paddle_tpu.incubate.autograd import Hessian, Jacobian
        x = paddle.to_tensor(np.array([1.0, 2.0, 3.0], np.float32))
        J = Jacobian(lambda v: v * v, x)
        np.testing.assert_allclose(np.asarray(J[:]._value),
                                   np.diag([2.0, 4.0, 6.0]), atol=1e-5)
        H = Hessian(lambda v: (v ** 3).sum(), x)
        np.testing.assert_allclose(np.asarray(H[:]._value),
                                   np.diag([6.0, 12.0, 18.0]), atol=1e-5)

    def test_batched_hessian(self):
        from paddle_tpu.incubate.autograd import Hessian
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randn(3, 2).astype(np.float32))
        H = Hessian(lambda v: (v ** 2).sum(), x, is_batched=True)
        got = np.asarray(H[:]._value)
        assert got.shape == (3, 2, 2)
        for b in range(3):
            np.testing.assert_allclose(got[b], 2.0 * np.eye(2), atol=1e-5)


class TestUtils:
    def test_dlpack_roundtrip(self):
        from paddle_tpu.utils import dlpack
        x = paddle.to_tensor(np.arange(6, dtype=np.float32).reshape(2, 3))
        cap = dlpack.to_dlpack(x)
        y = dlpack.from_dlpack(cap)
        np.testing.assert_array_equal(np.asarray(y._value),
                                      np.asarray(x._value))

    def test_dlpack_from_numpy_and_torch(self):
        from paddle_tpu.utils import dlpack
        y = dlpack.from_dlpack(np.arange(4).astype(np.float32))
        np.testing.assert_array_equal(np.asarray(y._value), [0, 1, 2, 3])
        torch = pytest.importorskip("torch")
        t = torch.arange(4, dtype=torch.float32)
        z = dlpack.from_dlpack(t)
        np.testing.assert_array_equal(np.asarray(z._value), [0, 1, 2, 3])

    def test_unique_name(self):
        from paddle_tpu.utils import unique_name
        a = unique_name.generate("layer")
        b = unique_name.generate("layer")
        assert a != b and a.startswith("layer_")
        with unique_name.guard():
            c = unique_name.generate("layer")
            assert c == "layer_0"
        d = unique_name.generate("layer")
        assert d != c or d.startswith("layer_")

    def test_try_import_and_deprecated(self):
        from paddle_tpu.utils import deprecated, try_import
        assert try_import("math") is math
        with pytest.raises(ImportError, match="not installed"):
            try_import("definitely_not_a_module_xyz")

        @deprecated(update_to="paddle.new_api", since="2.0")
        def old():
            return 42

        with pytest.warns(DeprecationWarning, match="new_api"):
            assert old() == 42

    def test_run_check(self, capsys):
        paddle.utils.run_check()
        assert "successfully" in capsys.readouterr().out


class TestOnnxShim:
    def test_export_raises_actionable(self):
        with pytest.raises(ImportError, match="jit.save"):
            paddle.onnx.export(None, "/tmp/x")


class TestDeviceNamespace:
    def test_queries(self):
        assert paddle.device.get_device().startswith(("cpu", "tpu"))
        assert paddle.device.get_device_count() >= 1
        assert paddle.device.cuda.device_count() == 0
        assert paddle.device.is_compiled_with_cuda() is False
        assert paddle.device.is_compiled_with_distribute() is True
        assert "cpu" in paddle.device.get_all_device_type()
        paddle.device.synchronize()  # no-throw


class TestRegularizer:
    def test_l2_decay_feeds_optimizer(self):
        from paddle_tpu import optimizer
        net = paddle.nn.Linear(4, 4)
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=net.parameters(),
                              weight_decay=paddle.regularizer.L2Decay(0.01))
        assert opt._weight_decay == 0.01

    def test_l1_decay_carries_coeff(self):
        r = paddle.regularizer.L1Decay(0.5)
        assert r.coeff == 0.5 and "L1Decay" in repr(r)


class TestCallbacksAndVersion:
    def test_callbacks_reexported(self):
        assert paddle.callbacks.EarlyStopping is not None
        assert paddle.callbacks.ModelCheckpoint is not None

    def test_version(self, capsys):
        assert paddle.version.full_version == paddle.__version__
        paddle.version.show()
        assert "full_version" in capsys.readouterr().out
        assert paddle.version.cuda() == "False"


class TestStaticNN:
    def test_fc_param_reuse(self):
        import paddle_tpu.static as st
        st.nn.static_param_store().clear()
        x = paddle.to_tensor(np.ones((2, 6), np.float32))
        a = st.nn.fc(x, 3, name="shared")
        b = st.nn.fc(x, 3, name="shared")
        np.testing.assert_array_equal(np.asarray(a._value),
                                      np.asarray(b._value))
        assert len(st.nn.static_param_store()) == 1

    def test_builders_shapes(self):
        import paddle_tpu.static as st
        st.nn.static_param_store().clear()
        rs = np.random.RandomState(0)
        img = paddle.to_tensor(rs.randn(2, 3, 8, 8).astype(np.float32))
        assert tuple(st.nn.conv2d(img, 4, 3).shape) == (2, 4, 6, 6)
        assert tuple(st.nn.batch_norm(img).shape) == (2, 3, 8, 8)
        assert tuple(st.nn.layer_norm(img, begin_norm_axis=2).shape) \
            == (2, 3, 8, 8)
        ids = paddle.to_tensor(np.array([[1, 2], [3, 4]], np.int32))
        assert tuple(st.nn.embedding(ids, (10, 5)).shape) == (2, 2, 5)
        assert tuple(st.nn.prelu(img, mode="channel").shape) == (2, 3, 8, 8)

    def test_control_flow_traced(self):
        import jax
        import paddle_tpu.static as st

        def f(x):
            big = st.nn.cond(x.sum() > 3.0, lambda: x * 10.0,
                             lambda: x * -1.0)
            i, acc = st.nn.while_loop(
                lambda i, acc: i < 3,
                lambda i, acc: (i + 1, acc + big.sum()),
                [paddle.to_tensor(0), paddle.to_tensor(0.0)])
            return acc._value

        got = jax.jit(lambda v: f(paddle.to_tensor(v)))(
            np.ones(4, np.float32))
        np.testing.assert_allclose(np.asarray(got), 120.0)
        got2 = jax.jit(lambda v: f(paddle.to_tensor(v)))(
            np.ones(2, np.float32))
        np.testing.assert_allclose(np.asarray(got2), -6.0)

    def test_switch_case_and_case(self):
        import paddle_tpu.static as st
        r = st.nn.switch_case(1, [lambda: paddle.to_tensor(5.0),
                                  lambda: paddle.to_tensor(7.0)])
        assert float(r._value) == 7.0
        r2 = st.nn.case([(paddle.to_tensor(False), lambda: paddle.to_tensor(1.0)),
                         (paddle.to_tensor(True), lambda: paddle.to_tensor(2.0))],
                        default=lambda: paddle.to_tensor(3.0))
        assert float(r2._value) == 2.0


class TestNNUtils:
    def test_weight_norm_roundtrip_and_grads(self):
        from paddle_tpu.nn import utils as U
        lin = paddle.nn.Linear(4, 3)
        w0 = np.asarray(lin.weight._value).copy()
        U.weight_norm(lin, "weight", dim=0)
        np.testing.assert_allclose(np.asarray(lin.weight._value), w0,
                                   rtol=1e-5)
        names = [n for n, _ in lin.named_parameters()]
        assert "weight_g" in names and "weight_v" in names \
            and "weight" not in names
        loss = (lin(paddle.to_tensor(
            np.ones((2, 4), np.float32))) ** 2).sum()
        loss.backward()
        assert lin.weight_g.grad is not None
        assert lin.weight_v.grad is not None
        U.remove_weight_norm(lin, "weight")
        np.testing.assert_allclose(np.asarray(lin.weight._value), w0,
                                   rtol=1e-5)
        assert "weight" in [n for n, _ in lin.named_parameters()]

    def test_weight_norm_trains_compiled(self):
        from paddle_tpu import optimizer
        from paddle_tpu.nn import utils as U
        net = paddle.nn.Linear(4, 2)
        U.weight_norm(net, "weight")
        opt = optimizer.AdamW(learning_rate=1e-2,
                              parameters=net.parameters())
        step = paddle.jit.TrainStep(
            net, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt)
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randn(8, 4).astype(np.float32))
        y = paddle.to_tensor(rs.randn(8, 2).astype(np.float32))
        l0 = float(step(x, y)._value)
        for _ in range(15):
            l1 = float(step(x, y)._value)
        assert l1 < l0

    def test_spectral_norm_unit_sigma(self):
        from paddle_tpu.nn import utils as U
        lin = paddle.nn.Linear(8, 8)
        U.spectral_norm(lin, "weight", n_power_iterations=5)
        out = lin(paddle.to_tensor(np.ones((1, 8), np.float32)))
        s = np.linalg.svd(np.asarray(lin.weight._value),
                          compute_uv=False)
        assert abs(s[0] - 1.0) < 0.05
        (out ** 2).sum().backward()
        assert lin.weight_orig.grad is not None

    def test_clip_grad_norm_and_value(self):
        from paddle_tpu.nn import utils as U
        p = paddle.to_tensor(np.ones(3, np.float32), stop_gradient=False)
        (p * p * 50).sum().backward()
        total = U.clip_grad_norm_([p], max_norm=1.0)
        assert float(total._value) > 1.0
        assert abs(np.linalg.norm(np.asarray(p.grad._value)) - 1.0) < 1e-4
        q = paddle.to_tensor(np.ones(2, np.float32), stop_gradient=False)
        (q * 10).sum().backward()
        U.clip_grad_value_([q], 0.5)
        np.testing.assert_allclose(np.asarray(q.grad._value), [0.5, 0.5])

    def test_vector_roundtrip(self):
        from paddle_tpu.nn import utils as U
        net = paddle.nn.Linear(3, 2)
        vec = U.parameters_to_vector(net.parameters())
        assert tuple(vec.shape) == (3 * 2 + 2,)
        vals = [np.asarray(p._value).copy() for p in net.parameters()]
        U.vector_to_parameters(vec * 2.0, net.parameters())
        for p, v in zip(net.parameters(), vals):
            np.testing.assert_allclose(np.asarray(p._value), v * 2.0,
                                       rtol=1e-6)
        with pytest.raises(ValueError, match="length"):
            U.vector_to_parameters(
                paddle.to_tensor(np.ones(3, np.float32)),
                net.parameters())


class TestReviewR5Fixes:
    def test_weight_readable_after_compiled_step(self):
        """Review: the weight-norm hook must not leak a tracer into the
        layer's weight cache when forward runs under jit."""
        from paddle_tpu import optimizer
        from paddle_tpu.nn import utils as U
        net = paddle.nn.Linear(4, 2)
        U.weight_norm(net, "weight")
        opt = optimizer.AdamW(learning_rate=1e-2,
                              parameters=net.parameters())
        step = paddle.jit.TrainStep(
            net, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt)
        rs = np.random.RandomState(0)
        step(paddle.to_tensor(rs.randn(8, 4).astype(np.float32)),
             paddle.to_tensor(rs.randn(8, 2).astype(np.float32)))
        w = np.asarray(net.weight._value)   # raised TracerArrayConversion
        assert w.shape == (4, 2)

    def test_spectral_norm_zero_iterations(self):
        from paddle_tpu.nn import utils as U
        lin = paddle.nn.Linear(6, 6)
        U.spectral_norm(lin, "weight", n_power_iterations=0)
        out = lin(paddle.to_tensor(np.ones((1, 6), np.float32)))
        assert np.isfinite(np.asarray(out._value)).all()

    def test_destroy_subgroup_keeps_world(self):
        import paddle_tpu.distributed as dist
        dist.init_parallel_env()
        g = dist.new_group(ranks=[0])
        dist.destroy_process_group(g)
        assert dist.is_initialized()
        dist.destroy_process_group()
        assert not dist.is_initialized()

    def test_multi_step_cached_per_k(self):
        from paddle_tpu import optimizer
        net = paddle.nn.Linear(4, 1)
        opt = optimizer.SGD(learning_rate=0.1,
                            parameters=net.parameters())
        step = paddle.jit.TrainStep(net, lambda m, x: m(x).sum(), opt)
        assert step.multi_step(2) is step.multi_step(2)
        assert step.multi_step(3) is not step.multi_step(2)

    def test_static_nn_unnamed_creates_fresh(self):
        """Documented reference semantics: unnamed builder calls create
        new parameters (named calls share — tested above)."""
        import paddle_tpu.static as st
        st.nn.static_param_store().clear()
        x = paddle.to_tensor(np.ones((1, 4), np.float32))
        st.nn.fc(x, 2)
        st.nn.fc(x, 2)
        assert len(st.nn.static_param_store()) == 2


class TestHub:
    def test_local_hubconf(self, tmp_path):
        (tmp_path / "hubconf.py").write_text(
            "def tiny(out_features=2):\n"
            "    'A tiny linear model.'\n"
            "    import paddle_tpu as paddle\n"
            "    return paddle.nn.Linear(4, out_features)\n")
        names = paddle.hub.list(str(tmp_path))
        assert "tiny" in names
        assert "tiny linear" in paddle.hub.help(str(tmp_path), "tiny")
        m = paddle.hub.load(str(tmp_path), "tiny", out_features=3)
        assert tuple(m(paddle.to_tensor(
            np.ones((1, 4), np.float32))).shape) == (1, 3)

    def test_remote_sources_refused(self):
        with pytest.raises(RuntimeError, match="network"):
            paddle.hub.list("user/repo", source="github")

    def test_weight_norm_dim1_size1_roundtrip(self):
        """Review r5: remove_weight_norm must use the RECORDED dim, not
        re-infer it (size-1 normed axes mis-inferred)."""
        from paddle_tpu.nn import utils as U
        lin = paddle.nn.Linear(4, 1)
        w0 = np.asarray(lin.weight._value).copy()
        U.weight_norm(lin, "weight", dim=1)
        U.remove_weight_norm(lin, "weight")
        np.testing.assert_allclose(np.asarray(lin.weight._value), w0,
                                   rtol=1e-5, atol=1e-7)


class TestGradHooksAndAliases:
    def test_register_hook_observe_and_replace(self):
        x = paddle.to_tensor(np.array([1.0, 2.0], np.float32),
                             stop_gradient=False)
        seen = {}
        x.register_hook(lambda g: seen.setdefault(
            "g", np.asarray(g._value)))
        (x * 3.0).sum().backward()
        np.testing.assert_allclose(seen["g"], [3.0, 3.0])
        np.testing.assert_allclose(np.asarray(x.grad._value), [3.0, 3.0])

        y = paddle.to_tensor(np.array([1.0], np.float32),
                             stop_gradient=False)
        y.register_hook(lambda g: g * 10.0)
        (y * 2.0).sum().backward()
        np.testing.assert_allclose(np.asarray(y.grad._value), [20.0])

    def test_register_hook_intermediate_and_remove(self):
        a = paddle.to_tensor(np.array([2.0], np.float32),
                             stop_gradient=False)
        b = a * 3.0
        b.register_hook(lambda g: g * 100.0)
        (b * 1.0).sum().backward()
        np.testing.assert_allclose(np.asarray(a.grad._value), [300.0])

        c = paddle.to_tensor(np.array([1.0], np.float32),
                             stop_gradient=False)
        h = c.register_hook(lambda g: g * 5.0)
        h.remove()
        (c * 2.0).sum().backward()
        np.testing.assert_allclose(np.asarray(c.grad._value), [2.0])

    def test_register_hook_requires_grad(self):
        t = paddle.to_tensor(np.ones(2, np.float32))
        with pytest.raises(RuntimeError, match="stop_gradient"):
            t.register_hook(lambda g: g)

    def test_namespace_aliases(self):
        import paddle_tpu.distributed.fleet as fleet
        import paddle_tpu.nn as nn
        assert nn.quant.weight_only_linear is not None
        assert nn.quant.weight_quantize is not None
        assert callable(fleet.utils.recompute)

    def test_hook_fires_once_with_accumulated_grad(self):
        """Review r5: a multi-use tensor's hook gets the ACCUMULATED
        gradient once, not per-edge partials."""
        x = paddle.to_tensor(np.array([1.0], np.float32),
                             stop_gradient=False)
        calls = []
        x.register_hook(lambda g: calls.append(np.asarray(g._value)))
        (x * 2.0 + x * 3.0).sum().backward()
        assert len(calls) == 1
        np.testing.assert_allclose(calls[0], [5.0])
        # non-linear hook sees the total (clip(5)=4, not clip2+clip3=5)
        y = paddle.to_tensor(np.array([1.0], np.float32),
                             stop_gradient=False)
        y.register_hook(lambda g: g.clip(max=4.0))
        (y * 2.0 + y * 3.0).sum().backward()
        np.testing.assert_allclose(np.asarray(y.grad._value), [4.0])

    def test_hook_on_backward_root_fires_with_seed(self):
        a = paddle.to_tensor(np.array([1.0], np.float32),
                             stop_gradient=False)
        b = a * 2.0
        b.register_hook(lambda g: g * 10.0)
        b.backward()
        np.testing.assert_allclose(np.asarray(a.grad._value), [20.0])

    def test_stale_handle_remove_is_noop(self):
        t = paddle.to_tensor(np.array([1.0], np.float32),
                             stop_gradient=False)
        h1 = t.register_hook(lambda g: g)
        h1.remove()
        t.register_hook(lambda g: g * 7.0)
        h1.remove()   # must not delete the newer hook
        (t * 1.0).sum().backward()
        np.testing.assert_allclose(np.asarray(t.grad._value), [7.0])

    def test_hook_with_paddle_grad_capture(self):
        from paddle_tpu.autograd import grad
        q = paddle.to_tensor(np.array([1.0], np.float32),
                             stop_gradient=False)
        q.register_hook(lambda g: g * 10.0)
        (gq,) = grad((q * 3.0).sum(), q)
        np.testing.assert_allclose(np.asarray(gq._value), [30.0])


class TestTopLevelModeAPIs:
    def test_paddle_grad_top_level(self):
        x = paddle.to_tensor(np.array([1.0, 2.0], np.float32),
                             stop_gradient=False)
        (g,) = paddle.grad((x * x).sum(), x)
        np.testing.assert_allclose(np.asarray(g._value), [2.0, 4.0])

    def test_static_mode_toggles(self):
        assert paddle.in_dynamic_mode()
        paddle.enable_static()
        try:
            assert not paddle.in_dynamic_mode()
        finally:
            paddle.disable_static()
        assert paddle.in_dynamic_mode()
