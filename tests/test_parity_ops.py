"""Numerics tests for the long-tail parity ops (ops/parity.py, sparse
additions, int8 primitives, packed flash wrappers) against numpy/scipy
references."""

import numpy as np
import pytest

import paddle_tpu as paddle

t = paddle.to_tensor
rng = np.random.default_rng(0)


class TestSpecialFunctions:
    def test_gammaln_vs_scipy(self):
        import scipy.special as ss

        x = np.abs(rng.normal(size=(16,))).astype(np.float32) + 0.1
        np.testing.assert_allclose(
            paddle.gammaln(t(x)).numpy(), ss.gammaln(x), rtol=1e-5, atol=1e-5
        )

    def test_gammaincc_and_bessel(self):
        import scipy.special as ss

        a = np.abs(rng.normal(size=(8,))).astype(np.float32) + 0.5
        x = np.abs(rng.normal(size=(8,))).astype(np.float32) + 0.5
        np.testing.assert_allclose(
            paddle.gammaincc(t(a), t(x)).numpy(), ss.gammaincc(a, x), rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(paddle.i0e(t(x)).numpy(), ss.i0e(x), rtol=1e-5)
        np.testing.assert_allclose(paddle.i1(t(x)).numpy(), ss.i1(x), rtol=1e-4)
        np.testing.assert_allclose(paddle.i1e(t(x)).numpy(), ss.i1e(x), rtol=1e-5)

    def test_polygamma(self):
        import scipy.special as ss

        x = np.abs(rng.normal(size=(8,))).astype(np.float32) + 0.5
        np.testing.assert_allclose(
            paddle.polygamma(t(x), 1).numpy(), ss.polygamma(1, x), rtol=1e-4
        )


class TestComplexViews:
    def test_roundtrip(self):
        x = rng.normal(size=(4, 3, 2)).astype(np.float32)
        c = paddle.as_complex(t(x))
        assert c.numpy().dtype == np.complex64
        np.testing.assert_allclose(paddle.as_real(c).numpy(), x, rtol=1e-6)

    def test_complex_build(self):
        r = rng.normal(size=(5,)).astype(np.float32)
        i = rng.normal(size=(5,)).astype(np.float32)
        np.testing.assert_allclose(paddle.complex(t(r), t(i)).numpy(), r + 1j * i)

    def test_complex_promotes_float64_to_complex128(self):
        import jax

        with jax.enable_x64(True):
            r = t(np.array([1.0, -2.0], np.float64))
            i = t(np.array([0.5, 3.0], np.float64))
            c = paddle.complex(r, i)
            assert c.numpy().dtype == np.complex128
            # mixed f32 x f64 promotes to the common (wider) type
            c2 = paddle.complex(t(np.float32([1.0])), t(np.float64([2.0])))
            assert c2.numpy().dtype == np.complex128

    def test_complex_half_inputs_take_float32_floor(self):
        # lax.complex only takes f32/f64 — halves must floor up, not raise
        c = paddle.complex(
            t(np.array([1.0], np.float16)), t(np.array([2.0], np.float16))
        )
        assert c.numpy().dtype == np.complex64
        np.testing.assert_allclose(c.numpy(), np.array([1 + 2j], np.complex64))

    def test_complex_integer_inputs_take_float32_floor(self):
        c = paddle.complex(t(np.array([1, 2], np.int32)), t(np.array([3, 4], np.int32)))
        assert c.numpy().dtype == np.complex64
        np.testing.assert_allclose(c.numpy(), np.array([1 + 3j, 2 + 4j], np.complex64))


class TestLinalgExtras:
    def test_lu_unpack_reconstructs(self):
        a = rng.normal(size=(5, 5)).astype(np.float32)
        lu, piv, _ = paddle.linalg.lu(t(a), get_infos=True)
        P, L, U = paddle.lu_unpack(lu, piv)
        rec = P.numpy() @ L.numpy() @ U.numpy()
        np.testing.assert_allclose(rec, a, rtol=1e-4, atol=1e-5)

    def test_diag_embed_and_fill_diagonal(self):
        v = rng.normal(size=(3, 4)).astype(np.float32)
        d = paddle.diag_embed(t(v))
        assert list(d.shape) == [3, 4, 4]
        np.testing.assert_allclose(np.diagonal(d.numpy(), axis1=-2, axis2=-1), v)
        m = paddle.fill_diagonal(t(np.zeros((4, 4), np.float32)), 3.0)
        np.testing.assert_allclose(np.diag(m.numpy()), np.full(4, 3.0))
        # offset diagonal
        off = paddle.diag_embed(t(v), offset=1)
        assert list(off.shape) == [3, 5, 5]

    def test_tri_indices_match_numpy(self):
        np.testing.assert_array_equal(
            paddle.tril_indices(4, 4, 0).numpy(), np.stack(np.tril_indices(4, 0, 4))
        )
        np.testing.assert_array_equal(
            paddle.triu_indices(3, 5, 1).numpy(), np.stack(np.triu_indices(3, 1, 5))
        )

    def test_pdist_cdist_vs_scipy(self):
        from scipy.spatial.distance import cdist as sp_cdist
        from scipy.spatial.distance import pdist as sp_pdist

        x = rng.normal(size=(6, 4)).astype(np.float32)
        y = rng.normal(size=(5, 4)).astype(np.float32)
        np.testing.assert_allclose(
            paddle.pdist(t(x)).numpy(), sp_pdist(x).astype(np.float32), rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            paddle.cdist(t(x), t(y)).numpy(), sp_cdist(x, y).astype(np.float32),
            rtol=1e-3, atol=1e-4,
        )
        np.testing.assert_allclose(
            paddle.cdist(t(x), t(y), p=1.0).numpy(),
            sp_cdist(x, y, metric="minkowski", p=1).astype(np.float32),
            rtol=1e-4, atol=1e-5,
        )

    def test_reduce_as(self):
        x = rng.normal(size=(4, 3, 5)).astype(np.float32)
        target = np.zeros((3, 1), np.float32)
        out = paddle.reduce_as(t(x), t(target))
        np.testing.assert_allclose(out.numpy(), x.sum(0).sum(-1, keepdims=True), rtol=1e-5)

    def test_norms(self):
        x = rng.normal(size=(3, 4)).astype(np.float32)
        np.testing.assert_allclose(
            float(paddle.squared_l2_norm(t(x)).numpy()), float((x**2).sum()), rtol=1e-5
        )
        np.testing.assert_allclose(
            paddle.p_norm(t(x), porder=3.0, axis=1).numpy(),
            (np.abs(x) ** 3).sum(1) ** (1 / 3), rtol=1e-4,
        )
        np.testing.assert_allclose(
            float(paddle.frobenius_norm(t(x)).numpy()), np.linalg.norm(x), rtol=1e-5
        )


class TestManipulationExtras:
    def test_index_fill(self):
        x = rng.normal(size=(4, 5)).astype(np.float32)
        out = paddle.index_fill(t(x), t(np.array([1, 3])), 0, 9.0).numpy()
        assert (out[[1, 3]] == 9.0).all() and (out[[0, 2]] == x[[0, 2]]).all()
        # method + inplace forms
        y = t(x.copy())
        y.index_fill_(t(np.array([0])), 1, -5.0)
        assert (y.numpy()[:, 0] == -5.0).all()

    def test_tensor_unfold_windows(self):
        x = np.arange(10, dtype=np.float32)
        w = t(x).unfold(0, 4, 3).numpy()
        np.testing.assert_array_equal(w, np.stack([x[0:4], x[3:7], x[6:10]]))

    def test_view_dtype_bitcast(self):
        x = np.array([1.0], np.float32)
        assert paddle.view_dtype(t(x), "int32").numpy()[0] == np.array([1.0], np.float32).view(np.int32)[0]

    def test_shape_fill_isempty(self):
        x = t(np.zeros((2, 3), np.float32))
        np.testing.assert_array_equal(paddle.shape(x).numpy(), [2, 3])
        assert paddle.fill(x, 4.0).numpy().max() == 4.0
        assert not bool(paddle.is_empty(x).numpy())


class TestDecodeOps:
    def test_viterbi_matches_bruteforce(self):
        import itertools

        B, T, N = 2, 4, 3
        pot = rng.normal(size=(B, T, N)).astype(np.float32)
        trans = rng.normal(size=(N, N)).astype(np.float32)
        score, path = paddle.viterbi_decode(t(pot), t(trans), include_bos_eos_tag=False)
        for b in range(B):
            best, best_path = -1e9, None
            for tags in itertools.product(range(N), repeat=T):
                s = pot[b, 0, tags[0]] + sum(
                    trans[tags[i - 1], tags[i]] + pot[b, i, tags[i]] for i in range(1, T)
                )
                if s > best:
                    best, best_path = s, tags
            np.testing.assert_allclose(float(score.numpy()[b]), best, rtol=1e-5)
            assert tuple(path.numpy()[b]) == best_path

    def test_edit_distance(self):
        h = np.array([[1, 2, 3, 0]], np.int64)
        r = np.array([[1, 3, 3, 4]], np.int64)
        d, n = paddle.edit_distance(t(h), t(r), normalized=False)
        assert float(d.numpy()[0, 0]) == 2.0  # substitute 2->3... wait: 1,2,3,0 vs 1,3,3,4
        dn, _ = paddle.edit_distance(
            t(np.array([[1, 2, 3]], np.int64)), t(np.array([[1, 2, 3]], np.int64)),
            normalized=False,
        )
        assert float(dn.numpy()[0, 0]) == 0.0

    def test_top_p_restricts_support(self):
        probs = np.array([[0.6, 0.3, 0.08, 0.02]], np.float32)
        for seed in range(1, 6):
            _, ids = paddle.top_p_sampling(t(probs), t(np.array([0.5], np.float32)), seed=seed)
            assert ids.numpy()[0, 0] == 0  # only the top token survives p=0.5

    def test_gather_tree_backtrace(self):
        # T=3, batch=1, beam=2; parents chain beam1@t2 -> beam0@t1 -> beam0@t0
        ids = np.array([[[1, 5]], [[2, 6]], [[3, 7]]], np.int64)
        parents = np.array([[[0, 1]], [[0, 0]], [[0, 0]]], np.int64)
        out = paddle.gather_tree(t(ids), t(parents)).numpy()
        np.testing.assert_array_equal(out[:, 0, 1], [1, 2, 7])


class TestSegmentOps:
    def test_segment_pool_modes(self):
        x = np.array([[1.0], [2.0], [4.0], [8.0]], np.float32)
        ids = np.array([0, 0, 1, 1], np.int32)
        assert paddle.segment_pool(t(x), t(ids), "SUM").numpy().ravel().tolist() == [3.0, 12.0]
        assert paddle.segment_pool(t(x), t(ids), "MEAN").numpy().ravel().tolist() == [1.5, 6.0]
        assert paddle.segment_pool(t(x), t(ids), "MAX").numpy().ravel().tolist() == [2.0, 8.0]

    def test_send_ue_recv(self):
        x = np.eye(3, dtype=np.float32)
        src = np.array([0, 1], np.int32)
        dst = np.array([2, 2], np.int32)
        e = np.array([[2.0], [3.0]], np.float32)
        out = paddle.send_ue_recv(t(x), t(e), t(src), t(dst), "MUL", "SUM").numpy()
        np.testing.assert_allclose(out[2], [2.0, 3.0, 0.0])


class TestVisionOps:
    def test_grid_sample_identity(self):
        x = rng.normal(size=(2, 3, 5, 5)).astype(np.float32)
        theta = np.tile(np.array([[[1, 0, 0], [0, 1, 0]]], np.float32), (2, 1, 1))
        grid = paddle.affine_grid(t(theta), [2, 3, 5, 5])
        out = paddle.grid_sample(t(x), grid).numpy()
        np.testing.assert_allclose(out, x, rtol=1e-4, atol=1e-5)

    def test_grid_sample_nearest_and_zeros_padding(self):
        x = np.arange(4, dtype=np.float32).reshape(1, 1, 2, 2)
        grid = np.array([[[[-1, -1], [3.0, 3.0]]]], np.float32)  # corner + out of bounds
        out = paddle.grid_sample(t(x), t(grid), mode="nearest").numpy()
        assert out[0, 0, 0, 0] == 0.0 and out[0, 0, 0, 1] == 0.0

    def test_nms_suppresses_overlaps(self):
        boxes = np.array(
            [[0, 0, 10, 10], [1, 1, 10.5, 10.5], [20, 20, 30, 30], [21, 21, 29, 29]],
            np.float32,
        )
        keep = paddle.nms(t(boxes), 0.5).numpy()
        assert keep[0] == 0 and keep[1] == 2 and (keep[2:] == -1).all()

    def test_nms_scores_sorts_internally_and_maps_back(self):
        """Reference ``paddle.vision.ops.nms(boxes, iou_threshold, scores)``:
        unsorted boxes + scores — nms runs in descending-score order and the
        returned indices point into the ORIGINAL box order."""
        boxes = np.array(
            [[1, 1, 10.5, 10.5], [20, 20, 30, 30], [0, 0, 10, 10], [21, 21, 29, 29]],
            np.float32,
        )
        scores = np.array([0.6, 0.9, 0.8, 0.3], np.float32)
        keep = paddle.nms(t(boxes), 0.5, scores=t(scores)).numpy()
        # score order: box1 (.9), box2 (.8), box0 (.6, IoU>0.5 with box2 ->
        # suppressed), box3 (IoU>0.5 with box1 -> suppressed)
        assert keep[0] == 1 and keep[1] == 2 and (keep[2:] == -1).all()

    def test_nms_without_scores_unchanged(self):
        boxes = np.array([[0, 0, 10, 10], [1, 1, 10.5, 10.5]], np.float32)
        keep = paddle.nms(t(boxes), 0.5).numpy()
        assert keep[0] == 0 and keep[1] == -1

    def test_matrix_nms_decays_overlaps(self):
        boxes = np.array([[0, 0, 10, 10], [0, 0, 10, 10], [50, 50, 60, 60]], np.float32)
        scores = np.array([0.9, 0.8, 0.7], np.float32)
        out, order = paddle.matrix_nms(t(boxes), t(scores))
        o = out.numpy()
        assert o[0] == pytest.approx(0.9)  # top box undamped
        assert o[1] < 0.1  # duplicate heavily decayed
        assert o[2] == pytest.approx(0.7, abs=1e-5)  # disjoint box untouched

    def test_roi_align_constant_region(self):
        x = np.full((1, 2, 8, 8), 3.0, np.float32)
        out = paddle.roi_align(t(x), t(np.array([[1, 1, 5, 5]], np.float32)), output_size=2)
        np.testing.assert_allclose(out.numpy(), np.full((1, 2, 2, 2), 3.0), rtol=1e-5)

    def test_roi_pool_picks_max(self):
        x = np.zeros((1, 1, 8, 8), np.float32)
        x[0, 0, 2, 2] = 5.0
        out = paddle.roi_pool(t(x), t(np.array([[0, 0, 7, 7]], np.float32)), output_size=1)
        assert float(out.numpy().max()) == 5.0

    def test_box_coder_roundtrip(self):
        priors = np.array([[0, 0, 10, 10], [5, 5, 15, 15]], np.float32)
        targets = np.array([[1, 1, 9, 9], [4, 6, 16, 14]], np.float32)
        enc = paddle.box_coder(t(priors), None, t(targets), "encode_center_size")
        dec = paddle.box_coder(t(priors), None, enc, "decode_center_size", axis=0)
        np.testing.assert_allclose(
            dec.numpy()[np.arange(2), np.arange(2)], targets, rtol=1e-4, atol=1e-4
        )

    def test_unpool_inverts_maxpool_positions(self):
        x = np.zeros((1, 1, 2, 2), np.float32)
        x[0, 0] = [[5.0, 1.0], [2.0, 3.0]]
        idx = np.array([[[[0, 3], [10, 15]]]], np.int64)  # flat positions in 4x4
        out = paddle.unpool(t(x), t(idx), kernel_size=2, stride=2).numpy()
        assert out[0, 0, 0, 0] == 5.0 and out[0, 0, 0, 3] == 1.0
        assert out[0, 0, 2, 2] == 2.0 and out[0, 0, 3, 3] == 3.0

    def test_temporal_shift_moves_channels(self):
        x = rng.normal(size=(4, 8, 2, 2)).astype(np.float32)
        out = paddle.temporal_shift(t(x), seg_num=2, shift_ratio=0.25).numpy()
        x5 = x.reshape(2, 2, 8, 2, 2)
        o5 = out.reshape(2, 2, 8, 2, 2)
        np.testing.assert_allclose(o5[:, 0, :2], x5[:, 1, :2])  # shifted back
        np.testing.assert_allclose(o5[:, 1, 2:4], x5[:, 0, 2:4])  # shifted forward
        np.testing.assert_allclose(o5[:, :, 4:], x5[:, :, 4:])  # untouched

    def test_prior_box_shapes(self):
        feat = t(np.zeros((1, 8, 4, 4), np.float32))
        img = t(np.zeros((1, 3, 32, 32), np.float32))
        boxes, var = paddle.prior_box(feat, img, min_sizes=[8.0], aspect_ratios=[2.0], clip=True)
        assert list(boxes.shape) == [4, 4, 2, 4]
        b = boxes.numpy()
        assert (b >= 0).all() and (b <= 1).all()


class TestMiscParity:
    def test_clip_by_norm(self):
        x = np.full((4,), 3.0, np.float32)  # norm 6
        out = paddle.clip_by_norm(t(x), 3.0).numpy()
        np.testing.assert_allclose(np.linalg.norm(out), 3.0, rtol=1e-5)
        same = paddle.clip_by_norm(t(x), 100.0).numpy()
        np.testing.assert_allclose(same, x)

    def test_add_position_encoding(self):
        x = np.zeros((1, 4, 8), np.float32)
        out = paddle.add_position_encoding(t(x), alpha=1.0, beta=1.0).numpy()
        np.testing.assert_allclose(out[0, 0, 4], 1.0, rtol=1e-5)  # cos(0)

    def test_spectral_norm_unit_sigma(self):
        w = rng.normal(size=(6, 4)).astype(np.float32)
        wn = paddle.spectral_norm(t(w), n_power_iterations=30).numpy()
        assert abs(np.linalg.svd(wn)[1][0] - 1.0) < 1e-3

    def test_random_families(self):
        d = paddle.dirichlet(t(np.full((4, 3), 2.0, np.float32))).numpy()
        np.testing.assert_allclose(d.sum(-1), np.ones(4), rtol=1e-5)
        g = paddle.standard_gamma(t(np.full((1000,), 2.0, np.float32))).numpy()
        assert abs(g.mean() - 2.0) < 0.3
        tr = paddle.truncated_gaussian_random((500,), a=-1.0, b=1.0).numpy()
        assert tr.min() >= -1.0 and tr.max() <= 1.0
        b = paddle.binomial(t(np.full((200,), 20.0, np.float32)), t(np.full((200,), 0.25, np.float32))).numpy()
        assert abs(b.mean() - 5.0) < 1.0


class TestNewOptimizers:
    @pytest.mark.parametrize("name", ["Ftrl", "DecayedAdagrad", "Dpsgd"])
    def test_decreases_loss(self, name):
        import paddle_tpu.nn.functional as F
        import paddle_tpu.optimizer as opt

        paddle.seed(0)
        lin = paddle.nn.Linear(4, 1)
        kwargs = {"sigma": 0.0} if name == "Dpsgd" else {}
        o = getattr(opt, name)(learning_rate=0.05, parameters=lin.parameters(), **kwargs)
        x = t(rng.normal(size=(16, 4)).astype(np.float32))
        y = t(rng.normal(size=(16, 1)).astype(np.float32))
        losses = []
        for _ in range(12):
            loss = F.mse_loss(lin(x), y)
            loss.backward()
            o.step()
            o.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0], f"{name} did not reduce loss: {losses}"


class TestInt8Primitives:
    def test_weight_quantize_roundtrip(self):
        import paddle_tpu.quantization as q

        w = rng.normal(size=(32, 16)).astype(np.float32)
        qw, sc = q.weight_quantize(t(w))
        assert qw.numpy().dtype == np.int8
        wd = q.weight_dequantize(qw, sc).numpy()
        assert np.abs(wd - w).max() < np.abs(w).max() / 100

    def test_weight_only_and_llm_int8_linear(self):
        import paddle_tpu.quantization as q

        w = rng.normal(size=(32, 16)).astype(np.float32)
        x = rng.normal(size=(4, 32)).astype(np.float32)
        b = rng.normal(size=(16,)).astype(np.float32)
        qw, sc = q.weight_quantize(t(w))
        ref = x @ w + b
        wol = q.weight_only_linear(t(x), qw, t(b), sc).numpy()
        i8 = q.llm_int8_linear(t(x), qw, t(b), sc).numpy()
        scale = np.abs(ref).max()
        assert np.abs(wol - ref).max() / scale < 0.02
        assert np.abs(i8 - ref).max() / scale < 0.03

    def test_llm_int8_uses_int32_accumulation(self):
        """The int8 path must contract in int8 (dot_general with int32
        accumulator), not silently upcast — check the jaxpr."""
        import jax
        import jax.numpy as jnp
        import paddle_tpu.quantization as q

        w = rng.normal(size=(8, 4)).astype(np.float32)
        qw, sc = q.weight_quantize(t(w))

        def f(xa):
            return q.llm_int8_linear(paddle.to_tensor(xa), qw, weight_scale=sc)._data

        jaxpr = str(jax.make_jaxpr(f)(jnp.ones((2, 8), jnp.float32)))
        assert "preferred_element_type=int32" in jaxpr


class TestSparseAdditions:
    def _coo(self):
        d = np.array([[1.0, 0, 2], [0, 3, 0]], np.float32)
        return d, paddle.to_tensor(d).to_sparse_coo()

    def test_unary_and_scale(self):
        import paddle_tpu.sparse as sp

        d, x = self._coo()
        np.testing.assert_allclose(sp.scale(x, 2.0).to_dense().numpy(), d * 2)
        np.testing.assert_allclose(sp.divide_scalar(x, 2.0).to_dense().numpy(), d / 2)
        assert sp.relu6(sp.scale(x, 5.0)).to_dense().numpy().max() == 6.0
        assert not sp.isnan(x).to_dense().numpy().any()

    def test_matvec_and_addmm(self):
        import paddle_tpu.sparse as sp

        d, x = self._coo()
        v = rng.normal(size=(3,)).astype(np.float32)
        np.testing.assert_allclose(sp.mv(x, t(v)).numpy(), d @ v, rtol=1e-5)
        dense = rng.normal(size=(3, 2)).astype(np.float32)
        inp = rng.normal(size=(2, 2)).astype(np.float32)
        np.testing.assert_allclose(
            sp.addmm(t(inp), x, t(dense), beta=0.5, alpha=2.0).numpy(),
            0.5 * inp + 2.0 * (d @ dense), rtol=1e-5,
        )

    def test_structure_ops(self):
        import paddle_tpu.sparse as sp

        d, x = self._coo()
        np.testing.assert_allclose(sp.reshape(x, [3, 2]).to_dense().numpy(), d.reshape(3, 2))
        np.testing.assert_allclose(
            sp.slice(x, [1], [1], [3]).to_dense().numpy(), d[:, 1:3]
        )
        np.testing.assert_allclose(
            sp.mask_as(t(np.full((2, 3), 7.0, np.float32)), x).to_dense().numpy(),
            7.0 * (d != 0),
        )

    def test_softmax_rows(self):
        import paddle_tpu.sparse as sp

        d, x = self._coo()
        sm = sp.softmax(x).to_dense().numpy()
        np.testing.assert_allclose(sm.sum(-1), np.ones(2), rtol=1e-5)
        assert sm[0, 1] == 0.0  # zeros stay zero


class TestPackedFlashWrappers:
    def test_qkvpacked_matches_unpacked(self):
        import paddle_tpu.nn.functional as F

        qkv = rng.normal(size=(2, 8, 3, 2, 4)).astype(np.float32)
        out_p, _ = F.flash_attn_qkvpacked(t(qkv), causal=True)
        out_u, _ = F.flash_attention(
            t(qkv[:, :, 0]), t(qkv[:, :, 1]), t(qkv[:, :, 2]), causal=True
        )
        np.testing.assert_allclose(out_p.numpy(), out_u.numpy(), rtol=1e-5, atol=1e-6)

    def test_fused_softmax_masks(self):
        import paddle_tpu.incubate.nn.functional as IF

        x = rng.normal(size=(1, 2, 4, 4)).astype(np.float32)
        up = IF.fused_softmax_mask_upper_triangle(t(x)).numpy()
        assert up[0, 0, 0, 1] == 0.0 and abs(up[0, 0, 0, 0] - 1.0) < 1e-6
        mask = np.zeros((1, 1, 4, 4), np.float32)
        sm = IF.fused_softmax_mask(t(x), t(mask)).numpy()
        np.testing.assert_allclose(sm.sum(-1), np.ones((1, 2, 4)), rtol=1e-5)


class TestReviewFixesR5:
    def test_fill_diagonal_non_square(self):
        out = paddle.fill_diagonal(t(np.zeros((2, 5), np.float32)), 1.0, offset=2).numpy()
        assert out[0, 2] == 1.0 and out[1, 3] == 1.0 and out.sum() == 2.0
        out = paddle.fill_diagonal(t(np.zeros((5, 2), np.float32)), 1.0, offset=-2).numpy()
        assert out[2, 0] == 1.0 and out[3, 1] == 1.0 and out.sum() == 2.0

    def test_viterbi_honors_lengths(self):
        B, T, N = 2, 6, 3
        pot = rng.normal(size=(B, T, N)).astype(np.float32)
        lens = np.array([3, 6], np.int32)
        s_pad, p_pad = paddle.viterbi_decode(
            t(pot), t(np.zeros((N, N), np.float32)), lengths=t(lens),
            include_bos_eos_tag=False,
        )
        # sequence 0 truncated at 3 must match decoding its 3-step slice alone
        s_short, p_short = paddle.viterbi_decode(
            t(pot[:1, :3]), t(np.zeros((N, N), np.float32)),
            include_bos_eos_tag=False,
        )
        np.testing.assert_allclose(float(s_pad.numpy()[0]), float(s_short.numpy()[0]), rtol=1e-5)
        np.testing.assert_array_equal(p_pad.numpy()[0, :3], p_short.numpy()[0])

    def test_zero_bubble_executor_rejects_small_M(self):
        import paddle_tpu.distributed as dist
        from paddle_tpu.models.gpt import GPTConfig, build_gpt_pipeline

        paddle.seed(0)
        cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=4, num_heads=2, max_position=32)
        pipe = build_gpt_pipeline(cfg, num_stages=4)
        mesh = dist.ProcessMesh(shape=[4], dim_names=["pp"])
        with pytest.raises(ValueError, match="zero_bubble"):
            pipe.build_spmd_executor(mesh, num_microbatches=2, schedule="zero_bubble")
