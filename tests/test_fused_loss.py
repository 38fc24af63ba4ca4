"""Fused linear + cross-entropy loss head: CPU-pinned numerics (scan
reference AND interpret-mode Pallas) vs the unfused ``lm_head +
F.cross_entropy`` composition, reduction/ignore_index semantics, the
``(loss, None)`` model contract, the ``FLAGS_use_fused_loss`` env seed, and
the compiled-peak-memory regression the no-materialization claim rests on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import memory as M
from paddle_tpu.flags import GLOBAL_FLAGS, FlagRegistry
from paddle_tpu.kernels import fused_loss as FL
from paddle_tpu.kernels.fused_loss import LossTiles, fused_linear_cross_entropy
from paddle_tpu.nn.functional.loss import cross_entropy

IGN = -100


def _data(n=48, h=64, v=1000, dtype=jnp.float32, seed=0, n_ignored=4):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, h)), dtype)
    w = jnp.asarray(rng.normal(size=(h, v)) * 0.05, dtype)
    lab = rng.integers(0, v, (n,)).astype(np.int32)
    if n_ignored:
        lab[rng.choice(n, n_ignored, replace=False)] = IGN
    return x, w, jnp.asarray(lab)


def _unfused(x, w, lab, reduction="mean"):
    return cross_entropy.raw_fn(x @ w, lab, ignore_index=IGN, reduction=reduction)


def _grads(fn, *args):
    return jax.value_and_grad(fn, argnums=(0, 1))(*args)


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk_eqns(sub)


class TestReferenceParity:
    """The lax.scan custom-VJP reference (the CPU/tier-1 path) vs unfused."""

    @pytest.mark.parametrize("v", [1000, 512, 130])  # incl. ragged vocab tails
    def test_loss_and_grads_fp32(self, v):
        x, w, lab = _data(v=v)
        lu, gu = _grads(_unfused, x, w, lab)
        lf, gf = _grads(lambda x, w: fused_linear_cross_entropy(x, w, lab), x, w)
        np.testing.assert_allclose(float(lf), float(lu), rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(gf[0]), np.asarray(gu[0]), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gf[1]), np.asarray(gu[1]), rtol=1e-4, atol=1e-5)

    def test_bf16_inputs(self):
        x, w, lab = _data(h=128, v=512, dtype=jnp.bfloat16)
        lu, gu = _grads(_unfused, x, w, lab)
        lf, gf = _grads(lambda x, w: fused_linear_cross_entropy(x, w, lab), x, w)
        assert lf.dtype == jnp.float32  # fp32 online logsumexp, fp32 loss
        np.testing.assert_allclose(float(lf), float(lu), rtol=1e-3, atol=1e-3)
        for got, ref in zip(gf, gu):
            assert got.dtype == ref.dtype  # grads land back in bf16
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(ref, np.float32),
                rtol=1e-2, atol=1e-2,
            )

    def test_tied_vocab_major_layout(self):
        x, w, lab = _data()
        lu, gu = _grads(_unfused, x, w, lab)
        lt, gt = _grads(
            lambda x, wv: fused_linear_cross_entropy(x, wv, lab, vocab_major=True),
            x, w.T,
        )
        np.testing.assert_allclose(float(lt), float(lu), rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(gt[0]), np.asarray(gu[0]), rtol=1e-4, atol=1e-5)
        # dW comes back in the embedding's [V, H] layout
        np.testing.assert_allclose(np.asarray(gt[1]), np.asarray(gu[1].T), rtol=1e-4, atol=1e-5)

    def test_all_rows_ignored(self):
        x, w, _ = _data()
        lab = jnp.full((x.shape[0],), IGN, jnp.int32)
        lf, gf = _grads(lambda x, w: fused_linear_cross_entropy(x, w, lab), x, w)
        assert float(lf) == 0.0  # mean denominator clamps at 1, like F.cross_entropy
        assert float(_unfused(x, w, lab)) == 0.0
        assert float(jnp.abs(gf[0]).max()) == 0.0
        assert float(jnp.abs(gf[1]).max()) == 0.0

    def test_mean_denominator_counts_only_valid(self):
        x, w, lab = _data(n_ignored=0)
        lab = lab.at[:30].set(IGN)  # 18 of 48 rows contribute
        ls = fused_linear_cross_entropy(x, w, lab, reduction="sum")
        lm = fused_linear_cross_entropy(x, w, lab, reduction="mean")
        np.testing.assert_allclose(float(lm), float(ls) / 18.0, rtol=1e-5)
        np.testing.assert_allclose(float(lm), float(_unfused(x, w, lab)), rtol=1e-3)

    def test_reduction_none_shape_and_values(self):
        x, w, lab = _data()
        per = fused_linear_cross_entropy(
            x.reshape(4, 12, -1), w, lab.reshape(4, 12), reduction="none"
        )
        assert per.shape == (4, 12)
        ref = _unfused(x, w, lab, reduction="none")
        np.testing.assert_allclose(np.asarray(per).ravel(), np.asarray(ref), rtol=1e-4, atol=1e-5)


# the block geometry's regimes (ISSUE 30): n, v, dtype, and the tiles the three
# kernels run (None: what ``_block_geometry`` derives for the shape)
_REGIMES = {
    # forward, dX and dW each on its own tile; rows pad 80 -> 96, vocab 1000 -> 1024
    "tiles_differ": dict(n=80, v=1000, block=LossTiles((32, 256), (16, 512), (48, 128))),
    "tiles_differ_bf16": dict(
        n=96, v=512, dtype=jnp.bfloat16, block=LossTiles((96, 128), (32, 256), (48, 512))
    ),
    # n and v not multiples of the tile (1000 % 128 != 0: ragged tail)
    "ragged_rows_and_vocab": dict(n=40, v=1000, block=(16, 128)),
    "ragged_rows_bf16": dict(n=40, v=256, dtype=jnp.bfloat16, block=(16, 128)),
    "even_tiles": dict(n=48, v=256, block=(16, 128)),
    # one row block, one vocab block: the geometry's answer for a small batch
    "one_row_block": dict(n=24, v=200, block=None),
    "one_row_block_bf16": dict(n=40, v=384, dtype=jnp.bfloat16, block=None),
    # several row blocks accumulate dW, several vocab blocks dX, in VMEM
    "many_blocks_each_way": dict(n=64, v=512, block=(16, 128)),
}


@pytest.fixture(params=["stored", "recomputed"])
def backward(request, monkeypatch):
    """Which backward the call builds: the one that stores ``d`` (it fits its
    share of device memory) or, with no memory to speak of, the one whose dW
    recomputes it. The shapes decide: nothing else is set."""
    if request.param == "recomputed":
        monkeypatch.setattr(FL, "_hbm_capacity", lambda: 0)
    return request.param


def _pallas_calls(fn, *args):
    """``{kernel name: (in avals, out avals)}`` of the ``pallas_call``s in ``fn``'s jaxpr."""
    calls = {}
    for eqn in _walk_eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name == "pallas_call":
            calls[eqn.params["name"]] = tuple(
                [(v.aval.shape, v.aval.dtype) for v in vs] for vs in (eqn.invars, eqn.outvars)
            )
    return calls


class TestPallasInterpretParity:
    """The Pallas kernels (fwd + dX + dW), interpret mode on CPU, against the
    scan reference: the same custom-VJP decomposition, the same roundings."""

    @pytest.mark.parametrize("vocab_major", [False, True], ids=["hidden_major", "vocab_major"])
    @pytest.mark.parametrize("regime", sorted(_REGIMES))
    def test_loss_and_grads_match_the_scan_reference(self, regime, vocab_major, backward):
        cfg = dict(_REGIMES[regime])
        block = cfg.pop("block")
        x, w, lab = _data(h=128, **cfg)
        wl = w.T if vocab_major else w
        bf16 = x.dtype == jnp.bfloat16

        def run(**kw):
            return _grads(
                lambda x, wl: fused_linear_cross_entropy(
                    x, wl, lab, vocab_major=vocab_major, **kw
                ),
                x, wl,
            )

        lr, gr = run()  # the scan reference (this backend's path)
        lp, gp = run(interpret=True, block=block)
        assert lp.dtype == jnp.float32
        np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5, atol=1e-5)
        for got, ref in zip(gp, gr):
            # dX and dW leave the kernels in the operand dtype (bf16: rounded
            # once, from the float32 sum in VMEM, where the reference rounds)
            assert got.dtype == ref.dtype and got.shape == ref.shape
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(ref, np.float32),
                rtol=2e-2 if bf16 else 1e-4, atol=2e-3 if bf16 else 1e-5,
            )
        # and against the plain composition
        lu, gu = _grads(_unfused, x, w, lab)
        np.testing.assert_allclose(float(lp), float(lu), rtol=1e-3, atol=1e-3)
        dw = gp[1].T if vocab_major else gp[1]
        np.testing.assert_allclose(
            np.asarray(dw, np.float32), np.asarray(gu[1], np.float32),
            rtol=1e-2 if bf16 else 1e-4, atol=1e-2 if bf16 else 1e-5,
        )

    def test_backward_kernels_hand_back_the_operand_dtype(self, backward):
        """dX and dW are accumulated in float32 VMEM scratch and written once:
        no float32 ``[N, H]`` or ``[H, V]`` leaves a kernel (ISSUE 30). Where
        ``d`` is stored it is dX's second output and dW's only operand beside
        x, in the operand dtype; where it is not, dW takes W, the labels, the
        logsumexp and the coefficient and forms it again (ISSUE 37)."""
        x, w, lab = _data(n=32, h=128, v=256, dtype=jnp.bfloat16)
        calls = _pallas_calls(
            jax.grad(
                lambda x, w: fused_linear_cross_entropy(
                    x, w, lab, interpret=True, block=(16, 128)
                ),
                argnums=(0, 1),
            ),
            x, w,
        )
        bf16 = jnp.bfloat16
        d = [((32, 256), bf16)] if backward == "stored" else []
        assert calls[FL.KERNEL_DX][1] == [((32, 128), bf16)] + d
        assert calls[FL.KERNEL_DW][1] == [((128, 256), bf16)]
        dw_in = calls[FL.KERNEL_DW][0]
        assert dw_in[0] == ((32, 128), bf16)
        if backward == "stored":
            assert dw_in[1:] == d
        else:
            cols = [((32, 1), t) for t in (jnp.int32, jnp.float32, jnp.float32)]
            assert dw_in[1:] == [((128, 256), bf16)] + cols
        assert all(t == jnp.float32 for _, t in calls[FL.KERNEL_FWD][1])  # m, l, target logit

    def test_all_ignored_interpret(self):
        x, w, _ = _data(h=128, v=256)
        lab = jnp.full((x.shape[0],), IGN, jnp.int32)
        lp, gp = _grads(
            lambda x, w: fused_linear_cross_entropy(
                x, w, lab, interpret=True, block=(16, 128)
            ),
            x, w,
        )
        assert float(lp) == 0.0
        assert float(jnp.abs(gp[0]).max()) == 0.0 and float(jnp.abs(gp[1]).max()) == 0.0


def _padded_data(n, v, dtype, seed=1):
    """Rows and vocabulary as the case wants them, four ``ignore_index`` rows."""
    return _data(n=n, h=128, v=v, dtype=dtype, seed=seed, n_ignored=4)


class TestStoredBlockGradient:
    """The backward forms each block's ``d = (softmax - onehot) * gcoef`` once
    where ``[n, v]`` of the operand dtype fits its share of device memory: dX
    writes the tile, dW reads it (ISSUE 37). It is the array both kernels
    computed before, so nothing may move, not by a bit."""

    @pytest.mark.parametrize("vocab_major", [False, True], ids=["hidden_major", "vocab_major"])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "float32"])
    @pytest.mark.parametrize(
        "n, v",
        [(64, 1000), (40, 512), (40, 1000), (64, 512)],
        ids=["vocab_pads", "rows_pad", "both_pad", "neither_pads"],
    )
    def test_dx_and_dw_equal_the_recompute_pairs_bit_for_bit(
        self, n, v, dtype, vocab_major, monkeypatch
    ):
        x, w, lab = _padded_data(n, v, dtype)
        wl = w.T if vocab_major else w
        # forward on its own tile; dX and dW share one, as d's logits are then
        # the same product in both kernels off the chip too (this backend's
        # float32 matmul sums in an order that follows the block's shape)
        block = LossTiles((32, 256), (16, 128), (16, 128))

        def grads():
            return _grads(
                lambda x, wl: fused_linear_cross_entropy(
                    x, wl, lab, vocab_major=vocab_major, interpret=True, block=block
                ),
                x, wl,
            )

        loss_s, (dx_s, dw_s) = grads()
        monkeypatch.setattr(FL, "_hbm_capacity", lambda: 0)
        loss_r, (dx_r, dw_r) = grads()
        assert float(jnp.abs(dx_s).max()) > 0 and float(jnp.abs(dw_s).max()) > 0
        assert float(loss_s) == float(loss_r)
        np.testing.assert_array_equal(np.asarray(dx_s, np.float32), np.asarray(dx_r, np.float32))
        np.testing.assert_array_equal(np.asarray(dw_s, np.float32), np.asarray(dw_r, np.float32))

    def test_the_shapes_choose_the_path_and_the_counter_names_it(self, monkeypatch):
        """``d`` of 64 x 512 float32 is exactly an eighth of the (patched)
        device memory and is stored; twice the rows are over it and recompute.
        No flag, no argument: the same call, other shapes."""
        from paddle_tpu.kernels import select

        monkeypatch.setattr(FL, "_hbm_capacity", lambda: 8 * 64 * 512 * 4)
        assert FL._stores_d(64, 512, 4) and not FL._stores_d(128, 512, 4)
        assert FL._stores_d(128, 512, 2)  # the operand dtype counts: bf16 halves d

        def built(n):
            x, w, lab = _padded_data(n, 512, jnp.float32)
            fn = jax.grad(
                lambda x, w: fused_linear_cross_entropy(x, w, lab, interpret=True, block=(16, 128)),
                argnums=(0, 1),
            )
            before = select.loss_backward_counts()
            calls = _pallas_calls(fn, x, w)
            after = select.loss_backward_counts()
            counted = {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)}
            return len(calls[FL.KERNEL_DX][1]), len(calls[FL.KERNEL_DW][0]), counted

        prior = paddle.get_flags(["FLAGS_enable_metrics"])
        paddle.set_flags({"FLAGS_enable_metrics": True})
        try:
            assert built(64) == (2, 2, {"stored": 1})  # dX -> (dX, d); dW <- (x, d)
            assert built(128) == (1, 5, {"recomputed": 1})  # dW <- (x, W, lab, lse, gcoef)
        finally:
            paddle.set_flags(prior)

    def test_device_memory_is_read_from_the_chip_or_taken_as_a_v5es(self, monkeypatch):
        from jax.experimental.pallas import tpu as pltpu

        assert FL._hbm_capacity() == 16 << 30  # no TPU here: a v5e's
        monkeypatch.setattr(
            pltpu, "get_tpu_info", lambda: type("Info", (), {"hbm_capacity_bytes": 32 << 30})()
        )
        assert FL._hbm_capacity() == 32 << 30
        # the train cell's d (1 GiB) fits an eighth of either; Llama-3's
        # vocabulary at the same batch in float32 (7.8 GiB) fits neither
        assert FL._stores_d(16384, 32768, 2) and not FL._stores_d(16384, 128256, 4)


RIDGE = 197e12 / 819e9  # a v5e's bf16 flops per HBM byte (240): under it a kernel waits for HBM


class TestBlockGeometry:
    """``_block_geometry``: each kernel's tile from the call's shapes (ISSUE 30).
    Forward and dX keep a row block of x and stream W past it, dW keeps a vocab
    block of W and streams x: the kept side sets the flops a streamed byte buys."""

    @staticmethod
    def _intensity(tiles, h, x_item, w_item):
        return {
            "fwd": 2 * tiles.fwd[0] / w_item,  # one matmul over each W block read
            "dx": 4 * tiles.dx[0] / w_item,  # two: the logits again, then dX
            # one matmul (d is stored at this size) over each x block read and d read once
            "dw": 2 / (x_item * (1 / tiles.dw[1] + 1 / h)),
        }

    @pytest.mark.parametrize("itemsize", [1, 2, 4], ids=["int8_w", "bf16", "float32"])
    @pytest.mark.parametrize("h", [1536, 2048, 4096, 8192])
    def test_streams_at_the_ridge_wherever_vmem_allows(self, h, itemsize):
        n, v = 16384, 32768
        x_item = max(itemsize, 2)  # the int8 walk: bf16 activations, int8 weight
        tiles = FL._block_geometry(n, v, h, x_item, itemsize)
        budget = FL._vmem_budget()
        for kernel, (br, bv) in tiles._asdict().items():
            assert n % br == 0 and v % bv == 0 and bv % 128 == 0 and br % 16 == 0
            need = FL._vmem_need(kernel, br, bv, h, x_item, itemsize)
            assert need <= budget, (kernel, need)
        for kernel, flops_per_byte in self._intensity(tiles, h, x_item, itemsize).items():
            if flops_per_byte >= RIDGE:
                continue
            # under the ridge only where the next kept size up does not fit
            br, bv = getattr(tiles, kernel)
            grown = (br, 2 * bv) if kernel == "dw" else (2 * br, bv)
            assert FL._vmem_need(kernel, *grown, h, x_item, itemsize) > budget, (kernel, tiles)

    def test_the_train_cell_reads_its_weight_32_times_not_128(self):
        """Mistral's width took (128, 128) from the 16 MiB guess: W read 128
        times a forward. The geometry keeps >= 512 rows where W streams and
        >= 512 vocab columns where x streams, and asks for the VMEM that takes."""
        tiles = FL._block_geometry(16384, 32768, 4096, 2, 2)
        assert tiles.fwd[0] >= 512 and tiles.dx[0] >= 512 and tiles.dw[1] >= 512
        need = FL._vmem_need("dx", *tiles.dx, 4096, 2, 2)
        assert FL._params(need).vmem_limit_bytes == need > 16 << 20  # over Mosaic's default: stated
        assert FL._params(8 << 20).vmem_limit_bytes is None  # under it: the default stands

    def test_dw_takes_the_tile_of_the_kernel_that_will_run(self, monkeypatch):
        """The one-matmul dW holds no weight block and no float32 logits tiles,
        and at 1024 vocab columns reads x half as often (22.96 ms against 23.37
        at the train cell's shapes on the chip, PERF.md, PR 37); the dW that
        recomputes ``d`` keeps the 512 x 512 it had (512 x 1024 is at the edge
        of its VMEM budget and read 59 and 45 ms in two calls)."""
        sizes = (16384, 32768, 4096, 2, 2)
        assert FL._block_geometry(*sizes) == LossTiles((512, 1024), (512, 512), (512, 1024))
        assert FL._vmem_need("dw", 512, 1024, 4096, 2, 2) < FL._vmem_need("dw_recompute", 512, 1024, 4096, 2, 2)
        monkeypatch.setattr(FL, "_hbm_capacity", lambda: 0)
        assert FL._block_geometry(*sizes) == LossTiles((512, 1024), (512, 512), (512, 512))

    @pytest.mark.parametrize(
        "n, v, rows, cols",
        [
            (24, 200, 32, 256),  # a small batch: one row block, one vocab block
            (16384, 49152, 512, 512),  # Ouro's vocabulary
            (2100, 32000, 432, 256),  # 5 x 432 rows (not 4 x 512 + 52); 125 x 256 divides Llama's vocabulary
            (600, 50257, 304, 512),  # 2 x 304; a prime-ish vocabulary pads 99 x 512
        ],
    )
    def test_blocks_are_cut_evenly_and_divide_the_padded_operands(self, n, v, rows, cols):
        tiles = FL._block_geometry(n, v, 2048, 2, 2)
        fitted, n_pad, vp = FL._fit_tiles(tiles, n, v)
        assert fitted == tiles
        for br, bv in tiles:
            assert n_pad % br == 0 and vp % bv == 0
            assert br % rows == 0 and bv % cols == 0  # whole multiples of the shared cut
        assert n_pad - n < rows and vp - v < cols

    @pytest.mark.parametrize("dw", ["dw", "dw_recompute"])
    def test_autotune_candidates_are_what_the_geometry_admits(self, dw):
        """At Mistral's width the old candidate list was EMPTY (every tile over
        the 16 MiB guess), so tuning never ran."""
        cands = FL._admitted_tiles(4096, 2, 2, dw)
        assert LossTiles(*(((512, 512),) * 3)) in cands and len(cands) >= 4
        budget = FL._vmem_budget()
        for t in cands:
            assert all(
                FL._vmem_need(k, *tile, 4096, 2, 2) <= budget
                for k, tile in zip(("fwd", "dx", dw), t)
            )
        assert FL._admitted_tiles(1 << 17, 4, 4, dw) == []  # nothing fits: default only


class TestModelContract:
    """Models return (loss, None) on the fused path, (loss, logits) off it."""

    def _llama(self, tie):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        paddle.seed(0)
        cfg = LlamaConfig.tiny()
        cfg.tie_word_embeddings = tie
        model = LlamaForCausalLM(cfg)
        rng = np.random.default_rng(3)
        ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int32))
        return model, ids

    @pytest.mark.parametrize("tie", [False, True])
    def test_llama_fused_vs_unfused(self, tie):
        model, ids = self._llama(tie)
        prior = paddle.get_flags(["FLAGS_use_fused_loss"])
        try:
            paddle.set_flags({"FLAGS_use_fused_loss": True})
            loss_f, second = model(ids, labels=ids)
            assert second is None  # the contract: no [B, S, V] buffer to return
            loss_f.backward()
            head = model.lm_head.weight if not tie else model.llama.embed_tokens.weight
            assert head.grad is not None and float(head.grad.abs().sum()) > 0
            model.clear_gradients()
            paddle.set_flags({"FLAGS_use_fused_loss": False})
            loss_u, logits = model(ids, labels=ids)
            assert logits is not None
            np.testing.assert_allclose(float(loss_f), float(loss_u), rtol=1e-3, atol=1e-3)
        finally:
            paddle.set_flags(prior)

    def test_gpt_and_ernie_fused_paths(self):
        from paddle_tpu.models.ernie import ErnieConfig, ErnieModel
        from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

        prior = paddle.get_flags(["FLAGS_use_fused_loss"])
        rng = np.random.default_rng(4)
        ids = paddle.to_tensor(rng.integers(0, 128, (2, 16)).astype(np.int32))
        try:
            paddle.set_flags({"FLAGS_use_fused_loss": True})
            paddle.seed(0)
            gpt = GPTForPretraining(GPTConfig.tiny())
            loss, second = gpt(ids, labels=ids)
            assert second is None
            loss.backward()
            assert float(gpt.gpt.embeddings.word_embeddings.weight.grad.abs().sum()) > 0
            paddle.seed(0)
            ernie = ErnieModel(ErnieConfig.tiny())
            mlm = np.full((2, 16), IGN, np.int64)
            mlm[0, 3], mlm[1, 5] = 7, 9
            loss_f, pooled = ernie(ids, labels=paddle.to_tensor(mlm))
            assert tuple(pooled.shape) == (2, 64)
            paddle.set_flags({"FLAGS_use_fused_loss": False})
            loss_u, _ = ernie(ids, labels=paddle.to_tensor(mlm))
            np.testing.assert_allclose(float(loss_f), float(loss_u), rtol=1e-3, atol=1e-3)
        finally:
            paddle.set_flags(prior)


class TestAutotuneEntry:
    def test_entry_consults_tuner_for_blocks(self, monkeypatch):
        """When ``block`` isn't pinned, the entry asks the autotuner for the
        (row_block, vocab_block) pair (the flash_attention test pattern)."""
        from paddle_tpu.kernels import autotune as at

        seen = {}

        def fake_autotune(kernel, key, candidates, build, default, repeats=3):
            seen["kernel"], seen["key"] = kernel, key
            return (16, 128)

        monkeypatch.setattr(at, "autotune", fake_autotune)
        x, w, lab = _data(h=128, v=256)
        loss = fused_linear_cross_entropy(x, w, lab, interpret=True)
        assert np.isfinite(float(loss))
        assert seen["kernel"] == "fused_linear_xent"
        assert seen["key"][1] == 256  # vocab size in the cache key


class TestFallbackCounter:
    def test_warn_fallback_counts_per_kernel(self):
        """A Pallas failure degrading to the XLA path is scrapeable, not just
        a one-time log line."""
        from paddle_tpu.kernels import select

        prior = paddle.get_flags(["FLAGS_enable_metrics"])
        paddle.set_flags({"FLAGS_enable_metrics": True})
        try:
            before = select._fallbacks_total.value(kernel="flxent_probe")
            select.warn_fallback("flxent_probe", RuntimeError("boom"))
            select.warn_fallback("flxent_probe", RuntimeError("boom again"))
            assert select._fallbacks_total.value(kernel="flxent_probe") == before + 2
        finally:
            paddle.set_flags(prior)


class TestFlagEnvSeeding:
    """FLAGS_use_fused_loss seeds from the environment at first read
    (the test_observability.py pattern)."""

    def test_env_seeds_fresh_registry(self, monkeypatch):
        reg = FlagRegistry()
        reg.define("use_fused_loss", bool, True, "")
        monkeypatch.setenv("FLAGS_use_fused_loss", "false")
        assert reg.get("use_fused_loss") is False

    def test_flag_registered_with_default_on(self):
        assert isinstance(GLOBAL_FLAGS.get("use_fused_loss"), bool)


class TestCompiledMemoryRegression:
    """The no-materialization claim, enforced on what the compiler holds
    live: the jitted fused train loss's TEMPORARIES must stay below the
    unfused composition's by more than an ``[N, V]`` buffer (core/memory.py
    compiled stats, the test_memory.py methodology). It reads
    ``temp_size_in_bytes``: this backend's ``peak_memory_in_bytes`` is
    arguments + outputs only (2 361 380 = 1 181 696 + 1 179 676 + 8 for
    BOTH programs here, 30 bytes apart by an output tuple's layout), so it
    cannot see a temporary at all, which is what this test asserted on and
    failed on since the seed. For the Pallas kernels on a described v5e:
    tests/test_tpu_aot_compile.py::test_fused_loss_never_holds_the_logits."""

    def test_fused_peak_below_unfused(self):
        n, h, v = 512, 128, 4096
        x = jnp.zeros((n, h), jnp.bfloat16)
        w = jnp.zeros((h, v), jnp.bfloat16)
        lab = jnp.zeros((n,), jnp.int32)

        def unfused(x, w, lab):
            return _unfused(x, w, lab)

        def fused(x, w, lab):
            return fused_linear_cross_entropy(x, w, lab)

        def peak(fn):
            c = jax.jit(jax.value_and_grad(fn, argnums=(0, 1))).lower(x, w, lab).compile()
            return M.compiled_memory_stats(c)["temp_size_in_bytes"]

        p_unfused = peak(unfused)
        p_fused = peak(fused)
        # the unfused composition holds [N, V] logits (+ fp32 log_softmax
        # copies) live across backward; the fused path's largest loss-head
        # temp is one [N, block] chunk
        assert p_fused < p_unfused, (p_fused, p_unfused)
        # and not marginally: at this shape the gap is several [N, V] buffers
        assert p_unfused - p_fused > n * v * 2, (p_fused, p_unfused)
