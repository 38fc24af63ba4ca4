"""Ahead-of-time compiles for a described TPU v5e, at Llama-2-7B widths.

``jax.export`` (tests/test_pallas_export.py) lowers a kernel to a serialized
Mosaic module but never runs the chip's compiler; VMEM limits and several
layout checks fire only at compile. The TPU compiler is installed here and
compiles for a chip that is described, not attached, so each case below
raises exactly what the chip would raise — no hardware, ~2 s per kernel.

Every kernel on the two main paths (jitted train step, serving engine step)
is compiled directly at the shapes ``chip_smoke.py`` uses: the dispatch
helpers ask ``jax.default_backend()`` and would take their CPU branch here.

The topology is described inside a fixture (never at import, in a
``skipif`` or in ``parametrize`` arguments): only one process may load the
TPU library, and under xdist every worker imports every test file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# Llama-2-7B widths (LlamaConfig.llama2_7b)
HIDDEN, HEADS, HEAD_DIM, MLP, VOCAB = 4096, 32, 128, 11008, 32000
SEQ = 2048  # train sequence length
SLOTS, CHUNK = 8, 16  # engine step [max_slots, prefill_chunk]
NB, BS, MBS = 2048, 16, 128  # KV pool blocks, block size, blocks per sequence


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prior)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    # conftest pins "highest" for the CPU numerics tests; the chip runs the
    # default, and Mosaic refuses an fp32-precision matmul on bf16 operands
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel is really in there
    return compiled


def _grad_all(fn, n_float):
    """fwd+bwd wrt the first ``n_float`` args: an unused cotangent would let
    DCE prune a backward kernel out before the compiler ever checked it."""
    return jax.grad(
        lambda *a: fn(*a).astype(jnp.float32).sum(), argnums=tuple(range(n_float))
    )


BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8
_QKV = ((1, SEQ, HEADS, HEAD_DIM), BF16)


def _flash_attention(batch=1, seq=SEQ, kv_heads=HEADS, dtype=BF16):
    """Forward and both backward kernels at the blocks ``_block_geometry``
    derives for the shape: what holds its VMEM model to the chip's compiler."""
    from paddle_tpu.kernels.flash_attention import flash_attention_pallas

    fn = _grad_all(lambda q, k, v: flash_attention_pallas(q, k, v, causal=True), 3)
    kv = ((batch, seq, kv_heads, HEAD_DIM), dtype)
    return fn, (((batch, seq, HEADS, HEAD_DIM), dtype), kv, kv)


# the chat cell's engine step (benchmarks/workloads/mistral7b.serve_chat.json:
# Mistral-7B GQA, 16 slots, 4096-block pool, 256 blocks a sequence); its tp=4
# shard holds 2 of the 8 KV heads
_CHAT = dict(slots=16, hq=32, hkv=8, nb=4096, mbs=256)
_CHAT_TP4 = dict(slots=16, hq=8, hkv=2, nb=4096, mbs=256)


def _paged_chunk(kv_dtype, fused=False, slots=SLOTS, hq=HEADS, hkv=HEADS, nb=NB, mbs=MBS):
    from paddle_tpu.kernels.paged_attention import paged_flash_chunk

    q = ((slots, CHUNK, hq, HEAD_DIM), BF16)
    rope = (((slots, CHUNK, HEAD_DIM), BF16),) * 2 if fused else ()
    pool = ((nb, hkv, BS, HEAD_DIM), kv_dtype)
    scales = (((nb, hkv, BS), F32),) * 2 if kv_dtype == I8 else ()
    tail = (((slots, mbs), I32), ((slots,), I32), ((slots,), I32))

    def fn(q, *rest):
        r = len(rope)  # the rope rows, then the two pools, then the scale planes
        opt = dict(zip(("cos", "sin"), rest[:r]))
        opt.update(zip(("k_scale", "v_scale"), rest[r + 2 : r + 2 + len(scales)]))
        return paged_flash_chunk(q, *rest[r : r + 2], *rest[r + 2 + len(scales) :], **opt)

    return fn, (q, *rope, pool, pool, *scales, *tail)


def _rms_norm():
    from paddle_tpu.kernels.fused import fused_rms_norm_pallas

    fn = _grad_all(lambda x, w: fused_rms_norm_pallas(x, w, 1e-6), 2)
    return fn, (((1, SEQ, HIDDEN), BF16), ((HIDDEN,), BF16))


def _rope():
    from paddle_tpu.kernels.fused import fused_rope_pallas, rope_adjoint_pallas

    def fn(x, g, cos, sin):
        return fused_rope_pallas(x, cos, sin), rope_adjoint_pallas(g, cos, sin)

    tab = ((SEQ, HEAD_DIM), F32)
    return fn, (_QKV, _QKV, tab, tab)


def _rms_norm_residual(shape):
    from paddle_tpu.kernels.fused import (
        fused_rms_norm_residual_pallas,
        rms_norm_residual_adjoint_pallas,
    )

    def fn(x, res, w, g):
        y, r = fused_rms_norm_residual_pallas(x, res, w, 1e-6)
        return y, r, rms_norm_residual_adjoint_pallas(g, r, w, 1e-6)

    x = (shape, BF16)
    return fn, (x, x, ((HIDDEN,), BF16), x)


def _embed_rms_norm():
    from paddle_tpu.kernels.fused import fused_embed_rms_norm_pallas

    return (
        lambda ids, table, w: fused_embed_rms_norm_pallas(ids, table, w, 1e-6),
        (((SLOTS, CHUNK), I32), ((VOCAB, HIDDEN), BF16), ((HIDDEN,), BF16)),
    )


# the train cell's loss head (benchmarks/workloads/mistral7b.train_2k.json):
# 8 x 2048 tokens, Mistral's 32 768 vocabulary, hidden 4096
CELL_TOKENS, CELL_VOCAB = 8 * SEQ, 32768
LLAMA3_VOCAB = 128256  # d [16384, 128256] bf16 is 3.9 GiB: over an eighth of a v5e's 16


def _loss_grads(n, v, h, vocab_major, dtype):
    """Forward, dX and dW at the tiles ``_block_geometry`` derives for the
    shape, each asking for the ``vmem_limit_bytes`` its tile needs: what holds
    ``_vmem_need`` to the chip's compiler. The backward is the pair the shapes
    choose: dX storing ``d`` and a one-matmul dW where ``[n, v]`` fits its
    share of device memory (every case at the cells' widths), else the pair
    whose dW recomputes it (Llama-3's vocabulary at the train cell's batch)."""
    from paddle_tpu.kernels.fused_loss import _block_geometry, _pallas_path

    item = jnp.dtype(dtype).itemsize
    block = _block_geometry(n, v, h, item, item)

    def fn(x, w, lab):
        return jax.grad(
            lambda x, w: _pallas_path(
                x, w, lab, v=v, h=h, ignore_index=-100, reduction="mean",
                vocab_major=vocab_major, interpret=False, block=block,
            ),
            argnums=(0, 1),
        )(x, w)

    return fn


def _fused_loss(vocab_major=False, n=CELL_TOKENS, v=CELL_VOCAB, h=HIDDEN, dtype=BF16):
    w = (v, h) if vocab_major else (h, v)
    return _loss_grads(n, v, h, vocab_major, dtype), (((n, h), dtype), (w, dtype), ((n,), I32))


def _fused_loss_quant(n=CELL_TOKENS, v=CELL_VOCAB, h=HIDDEN):
    """The int8 forward walk: both operands are upcast in VMEM for the dot."""
    from paddle_tpu.kernels.fused_loss import _block_geometry, _pallas_quant_path

    block = _block_geometry(n, v, h, 2, 1, quantized=True)

    def fn(x, w, s, lab):
        return _pallas_quant_path(
            x, w, s, lab, v=v, h=h, ignore_index=-100, reduction="mean",
            vocab_major=False, interpret=False, block=block,
        )

    return fn, (((n, h), BF16), ((h, v), I8), ((v,), F32), ((n,), I32))


def _wo_matmul():
    from paddle_tpu.kernels.quant import _default_block, _wo_matmul_pallas

    m, k, n = 128, MLP, HIDDEN  # the down projection: K = 11008 = 2**8 * 43
    block = _default_block(m, k, n)
    return (
        lambda x, w8, s: _wo_matmul_pallas(x, w8, s, block),
        (((m, k), BF16), ((k, n), I8), ((n,), F32)),
    )


def _latent_chunk(chunk=16, slots=16, heads=128, width=640, value=512, bs=16, mbs=528):
    """The latent page walk at DeepSeek-V2's widths and the document cell's pool
    (``benchmarks/workloads/deepseekv2.serve_doc.json``): 128 heads over one
    640-lane row a token; at a chunk of 16 one cell holds all 2048 packed rows
    (what its ``vmem_limit_bytes`` is stated for), at 64 the heads split."""
    from paddle_tpu.kernels.paged_attention import paged_latent_chunk

    return (
        lambda q, pool, tables, lens, qlens: paged_latent_chunk(q, pool, tables, lens, qlens, value_width=value),
        (((slots, chunk, heads, width), BF16), ((slots * mbs, 1, bs, width), BF16), ((slots, mbs), I32),
         ((slots,), I32), ((slots,), I32)),
    )


CASES = {
    "flash_attention_fwd_bwd_s2048": _flash_attention,
    # the train cell (benchmarks/workloads/mistral7b.train_2k.json): batch 8, GQA 4:1, 512 x 512 blocks
    "flash_attention_fwd_bwd_train_cell_gqa": lambda: _flash_attention(batch=8, kv_heads=8),
    "flash_attention_fwd_bwd_s2048_float32": lambda: _flash_attention(batch=8, dtype=F32),
    # twice the sequence: the whole-sequence operands leave room for 256 x 512 only
    "flash_attention_fwd_bwd_s4096_gqa": lambda: _flash_attention(batch=2, seq=4096, kv_heads=8),
    "paged_flash_chunk_bf16": lambda: _paged_chunk(BF16),
    "paged_flash_chunk_int8": lambda: _paged_chunk(I8),
    "paged_flash_chunk_fused_bf16": lambda: _paged_chunk(BF16, fused=True),
    "paged_flash_chunk_fused_int8": lambda: _paged_chunk(I8, fused=True),
    "paged_flash_chunk_chat_bf16": lambda: _paged_chunk(BF16, **_CHAT),
    "paged_flash_chunk_fused_chat_bf16": lambda: _paged_chunk(BF16, fused=True, **_CHAT),
    "paged_flash_chunk_fused_chat_int8": lambda: _paged_chunk(I8, fused=True, **_CHAT),
    "paged_flash_chunk_fused_chat_scratch_pool": lambda: _paged_chunk(BF16, fused=True, **{**_CHAT, "nb": 256}),
    "paged_flash_chunk_fused_chat_tp4_shard_bf16": lambda: _paged_chunk(BF16, fused=True, **_CHAT_TP4),
    "paged_flash_chunk_fused_chat_tp4_shard_int8": lambda: _paged_chunk(I8, fused=True, **_CHAT_TP4),
    "paged_latent_chunk_doc_cell": _latent_chunk,
    "paged_latent_chunk_decode_only": lambda: _latent_chunk(chunk=1),
    "paged_latent_chunk_chunk64_heads_split": lambda: _latent_chunk(chunk=64),
    "fused_rms_norm_fwd_bwd": _rms_norm,
    "fused_rope_and_adjoint": _rope,
    "fused_rms_norm_residual_train": lambda: _rms_norm_residual((1, SEQ, HIDDEN)),
    "fused_rms_norm_residual_step": lambda: _rms_norm_residual((SLOTS, CHUNK, HIDDEN)),
    "fused_embed_rms_norm": _embed_rms_norm,
    # the train cell's shapes, both weight layouts
    "fused_loss_fwd_bwd_hidden_major": lambda: _fused_loss(False),
    "fused_loss_fwd_bwd_vocab_major": lambda: _fused_loss(True),
    # Ouro's width (hidden 2048, vocabulary 49 152), twice Mistral's, float32
    # operands, a batch that no block divides, a small one, the int8 walk
    "fused_loss_fwd_bwd_ouro_width": lambda: _fused_loss(h=2048, v=49152),
    "fused_loss_fwd_bwd_hidden_8192": lambda: _fused_loss(n=8192, h=8192),
    "fused_loss_fwd_bwd_float32": lambda: _fused_loss(dtype=F32),
    "fused_loss_fwd_bwd_ragged_llama_vocab": lambda: _fused_loss(n=2100, v=VOCAB),
    "fused_loss_fwd_bwd_one_row_block": lambda: _fused_loss(n=SLOTS * CHUNK),
    "fused_loss_fwd_bwd_llama3_vocab_recomputes_d": lambda: _fused_loss(v=LLAMA3_VOCAB),
    "fused_loss_fwd_quant_train_cell": _fused_loss_quant,
    "wo_int8_matmul_k11008": _wo_matmul,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e_at_7b_width(case, one_chip):
    fn, shapes = CASES[case]()
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("wrapped", [True, False], ids=["per_shard", "bare"])
def test_norm_kernel_under_a_four_chip_tp_mesh(wrapped, topo):
    """A bare ``pallas_call`` in a program partitioned over four chips is
    refused by Mosaic at lowering — the fact the dispatch's routing rests on
    (kernels/select.py). Wrapped by ``per_shard`` under the engine's tp mesh
    the same kernel compiles, each shard on its replicated copy."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from paddle_tpu.core.spmd import partitioned_trace
    from paddle_tpu.kernels.fused import fused_rms_norm_residual_pallas
    from paddle_tpu.kernels.select import per_shard

    mesh = Mesh(np.asarray(topo.devices[:4], dtype=object), ("tp",))
    replicated = NamedSharding(mesh, PartitionSpec())
    x = jax.ShapeDtypeStruct((SLOTS, CHUNK, HIDDEN), BF16, sharding=replicated)
    w = jax.ShapeDtypeStruct((HIDDEN,), BF16, sharding=replicated)

    def kernel(x, res, w):
        return fused_rms_norm_residual_pallas(x, res, w, 1e-6)

    if not wrapped:
        with pytest.raises(NotImplementedError, match="cannot be automatically partitioned"):
            jax.jit(kernel).lower(x, x, w)
        return
    with partitioned_trace(mesh):  # what the engine's dispatch arms
        compiled = jax.jit(lambda *a: per_shard(kernel)(*a)).lower(x, x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_the_shapes_choose_the_loss_heads_backward():
    from paddle_tpu.kernels.fused_loss import _stores_d

    assert _stores_d(CELL_TOKENS, CELL_VOCAB, 2) and _stores_d(CELL_TOKENS, 49152, 2)
    assert _stores_d(CELL_TOKENS, CELL_VOCAB, 4)  # float32: 2 GiB, the share itself
    assert not _stores_d(CELL_TOKENS, LLAMA3_VOCAB, 2)


@pytest.mark.parametrize("backward", ["stored", "recomputed"])
def test_fused_loss_holds_one_block_gradient_and_never_the_logits(backward, one_chip, monkeypatch):
    """What the fused loss head keeps in HBM, on the chip's compiler, at the
    train cell's shapes (8 x 2048 tokens, Mistral's 32 768 vocabulary). The
    plain ``cross_entropy(x @ W)`` composition keeps the ``[N, V]`` bf16
    logits for its backward (1 GiB of temporaries, and nothing else: the
    chip's compiler recomputes the float32 copies). The fused forward holds
    nothing of that size; its backward holds exactly ONE ``[n_pad, vp]``
    buffer of the operand dtype, the block gradients ``d`` that dX writes and
    dW reads (ISSUE 37: storing them costs 2.6 ms of HBM traffic, recomputing
    them a 22 ms matmul), and nothing else the size of an operand: dX and dW
    leave their kernels in bf16 (the float32 sums stay in VMEM). Where ``d``
    is over its share of device memory (here: no memory at all) the backward
    recomputes it and the compiler counts 0 bytes of temporaries, at any
    token count."""
    from paddle_tpu.kernels import fused_loss

    n, v = CELL_TOKENS, CELL_VOCAB
    if backward == "recomputed":
        monkeypatch.setattr(fused_loss, "_hbm_capacity", lambda: 0)
    fused = _loss_grads(n, v, HIDDEN, False, BF16)

    def plain(x, w, lab):
        def loss(x, w):
            logits = (x @ w).astype(F32)
            picked = jnp.take_along_axis(logits, lab[:, None], axis=-1)[:, 0]
            return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)

        return jax.grad(loss, argnums=(0, 1))(x, w)

    shapes = (((n, HIDDEN), BF16), ((HIDDEN, v), BF16), ((n,), I32))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    with jax.default_matmul_precision("default"):
        held_plain = jax.jit(plain).lower(*args).compile().memory_analysis().temp_size_in_bytes
    held_fused = _compile(fused, one_chip, *shapes).memory_analysis().temp_size_in_bytes
    assert held_plain >= n * v * 2, held_plain  # the logits
    d = n * v * 2 if backward == "stored" else 0
    # d, once, and not even half a bf16 x beside it: no float32 dX or dW
    assert d <= held_fused < d + n * HIDDEN * 2 / 2, held_fused


def test_the_latent_cells_step_compiles_at_published_widths(one_chip, monkeypatch):
    """The engine's ONE step program for ``DeepseekV2ForCausalLM`` at the
    published widths (the leading dense layer and one expert layer holding 8 of
    160 experts, an eighth of the vocabulary; the document cell's slots, chunk,
    pages and pool), lowered for the described chip with the dispatch on its
    Pallas branch and the caches donated as the engine donates them on a TPU:
    the latent walk is in it once a layer, beside the norm kernels, every plane
    is aliased into the result, and NO operation copies a plane (the fork, the
    append and the walk all work on the donated plane where it lies; a
    ``lax.cond`` around the fork cost two such copies a layer a step, PR 42)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.kernels.paged_attention import KERNEL_LATENT
    from paddle_tpu.models.deepseek_v2 import DeepseekV2Config, DeepseekV2ForCausalLM
    from paddle_tpu.nn import initializer

    # a leaf stays the zeros it is made as: drawing 0.7 B normals on the CPU buys a compile nothing
    monkeypatch.setattr(initializer.Normal, "__call__", lambda self, param, block=None: None)
    paddle.seed(0)
    config = DeepseekV2Config(num_hidden_layers=2, n_routed_experts=8, n_routed_experts_total=160, vocab_size=12800)
    model = DeepseekV2ForCausalLM(config)
    model.eval()
    eng = ContinuousBatchingEngine(model, max_slots=16, block_size=16, prompt_bucket=8192, max_model_len=8448,
                                   prefill_chunk=16)
    s, c, mbs = eng.max_slots, eng.prefill_chunk, eng.max_blocks_per_seq
    assert eng._caches[0][0].shape == (16 * 528, 1, 16, 640) and eng.pool_stats()["bytes_per_token"] == 2 * 1280
    args = (eng._param_arrays(), eng._caches, jnp.zeros((s, c), I32), jnp.zeros((s, mbs), I32), jnp.zeros((s,), I32),
            jnp.ones((s,), I32), jnp.ones((s,), bool), jnp.zeros((s,), I32), jnp.full((s,), eng.num_blocks, I32))
    shaped = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), args)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the dispatch takes its Pallas branch
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(eng._step_impl, donate_argnums=(1,)).lower(*shaped).compile()
    text = compiled.as_text()
    assert text.count(KERNEL_LATENT) >= 2 and text.count("tpu_custom_call") >= 2 + 4 + 4 + 1  # walks, norms, embed
    plane_copies = re.findall(r"= bf16\[8448,(?:1,)?16,640\]\S* copy\(", text)
    assert not plane_copies, plane_copies
    memory = compiled.memory_analysis()
    plane = 16 * 528 * 16 * 640 * 2
    assert memory.alias_size_in_bytes == 2 * plane and memory.temp_size_in_bytes < plane, memory


# --- the state-space scan's carried-state kernel (kernels/ssm_scan.py) -----------------------------------------

SSM_SLOTS, SSM_HEADS, SSM_HEAD_DIM, SSM_STATE, SSM_GROUPS = 32, 64, 64, 128, 8  # nemotron3nano.serve_chat


@pytest.mark.parametrize("rows", [16, 1])
def test_ssm_state_scan_compiles_at_the_hybrid_cells_widths(rows, one_chip):
    """The kernel at the hybrid cell's shapes (32 slots of 64 heads x [64, 128]
    float32), a chunk of 16 rows and a single decode row (``generate_paged``),
    with the plane donated: the compiled program aliases the whole plane into
    its result and holds no temporary of that size."""
    from paddle_tpu.kernels.ssm_scan import KERNEL_SCAN, ssm_state_scan

    s, h, p, n, g = SSM_SLOTS, SSM_HEADS, SSM_HEAD_DIM, SSM_STATE, SSM_GROUPS
    shapes = (((s, rows, g, n), F32), ((s, rows, g, n), F32), ((s, rows, h, p), F32), ((s, h), F32),
              ((s, h, p, n), F32), ((s,), jnp.bool_), ((s,), jnp.bool_))
    args = [jax.ShapeDtypeStruct(shape, d, sharding=one_chip) for shape, d in shapes]
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(ssm_state_scan, donate_argnums=(4,)).lower(*args).compile()
    assert compiled.as_text().count(KERNEL_SCAN) and "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    plane = s * h * p * n * 4
    assert memory.alias_size_in_bytes == plane and memory.temp_size_in_bytes < plane // 8, memory


def test_the_hybrid_step_updates_every_state_plane_in_place(one_chip, monkeypatch):
    """The engine's ONE step program for a small ``NemotronHForCausalLM`` whose
    state-space blocks have shapes the kernel takes (two heads of 64 a group,
    state 128), lowered for the described chip with the dispatch on its Pallas
    branch and the caches donated as the engine donates them on a TPU: the scan
    kernel is in it once an ``M`` block, every plane the engine keeps (KV pages,
    state, conv tail) is aliased into the result. (At this size the compiler
    stages a 1 MB plane through fast memory around the kernel; that a plane of
    the cell's size is handed over as it is, with no temporary, is the test above.)"""
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.kernels.ssm_scan import KERNEL_SCAN
    from paddle_tpu.models.nemotron_h import NemotronHConfig, NemotronHForCausalLM

    paddle.seed(0)
    config = NemotronHConfig(
        vocab_size=512, hidden_size=256, num_hidden_layers=4, hybrid_override_pattern="M*EM",
        num_attention_heads=4, num_key_value_heads=2, head_dim=128, mamba_num_heads=4, mamba_head_dim=64,
        n_groups=2, ssm_state_size=128, n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=128,
        moe_shared_expert_intermediate_size=128, max_position_embeddings=256,
    )
    model = NemotronHForCausalLM(config)
    model.eval()
    eng = ContinuousBatchingEngine(model, max_slots=8, block_size=16, prompt_bucket=128, max_model_len=256,
                                   prefill_chunk=16)
    s, c, mbs = eng.max_slots, eng.prefill_chunk, eng.max_blocks_per_seq
    caches = eng._caches + eng._states  # the step's flat argument: the paged sets, then the recurrent ones
    assert [tuple(planes[0].shape) for planes in eng._states] == [(8, 4, 64, 128)] * 2
    args = (eng._param_arrays(), caches, jnp.zeros((s, c), I32), jnp.zeros((s, mbs), I32), jnp.zeros((s,), I32),
            jnp.ones((s,), I32), jnp.ones((s,), bool), jnp.zeros((s,), I32), jnp.full((s,), eng.num_blocks, I32))
    shaped = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), args)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the dispatch takes its Pallas branch
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(eng._step_impl, donate_argnums=(1,)).lower(*shaped).compile()
    text = compiled.as_text()
    assert len(set(re.findall(rf"%({KERNEL_SCAN}[.\d]*) = ", text))) == 2, "one kernel an M block"
    # the name a device trace gives the kernel's events: what ``ssm_pct.serve`` finds ``ssm_mixer`` in
    assert f'op_name="jit(_step_impl)/ssm_mixer/ssm_scan/jit({KERNEL_SCAN})/{KERNEL_SCAN}/pallas_call"' in text
    kept = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == kept, (memory, kept)
