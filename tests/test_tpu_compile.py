"""The main path's Pallas kernels, compiled at real widths by the TPU
compiler for a described (not attached) v5e:2x2.

A compile that passes is not a chip run: nothing executes, so this says
nothing of results or times.  It does refuse what interpret mode lets
through — a misaligned slice, too much VMEM, a kernel the chip's
compiler will not lower — at no chip time, for every later PR.

The topology is described inside a fixture of THIS file only (one
process at a time may load the TPU library; see the on-chip-measurement
guide §2) and every compile runs in the test's own process.  Whole-step
compiles of the decode and train steps and of the 2x2 mesh take a minute
each and belong to a rehearsal script, not to tier-1; the one whole step
here is the narrow prefill pass's (6 to 15 s a configuration).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.paged_attention import paged_attention

# chip_smoke.py's serve phase: Llama-3-8B heads, page 16, a bf16 pool for
# max_batch 8 sequences of 8192 tokens plus the garbage page
B, H, HKV, D, PAGE = 8, 32, 8, 128, 16
POOL_SLOTS = (1 + B * (8192 // PAGE)) * PAGE
# its train phase: bench_1b heads at batch 8 x seq 1024
TRAIN_SHAPE = (8, 1024, 12, 4, 128)
LLAMA3_8B_SHAPE = (1, 1024, 32, 8, 128)


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on the described chip, with jax's persistent compile
    cache off while this file runs: such a compile can be written to the
    cache but not read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _qkv(shape, sharding):
    b, s, h, hkv, d = shape
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16, sharding=sharding)
    return q, kv, kv


# chip_smoke.py's 8 lanes at its narrowest and widest table, and the chat
# cell's 16 lanes (benchmarks/configs/mistral-7b-v0.3-serve.json: contexts
# to 4096) at every width its engine asks for; each width has its own
# pages a grid step, whose buffers must fit VMEM
@pytest.mark.parametrize("lanes,width", [
    (B, 4), (B, 512), (16, 4), (16, 16), (16, 64), (16, 256)],
    ids=["narrowest", "widest", "chat-4", "chat-16", "chat-64", "chat-256"])
def test_paged_decode_compiles_at_serve_shapes(one_chip, lanes, width):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, k, v, bt, cl: paged_attention(
            q, k, v, bt, cl, page_size=PAGE, interpret=False)
    ).lower(spec((lanes, 1, H, D), jnp.bfloat16),
            spec((POOL_SLOTS, HKV, D), jnp.bfloat16),
            spec((POOL_SLOTS, HKV, D), jnp.bfloat16),
            spec((lanes, width), jnp.int32),
            spec((lanes,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_attention_decode" in text
    assert "paged_attention_decode_window" not in text


@pytest.mark.parametrize("shape", [TRAIN_SHAPE, LLAMA3_8B_SHAPE],
                         ids=["train", "llama3_8b"])
def test_flash_forward_compiles(one_chip, shape):
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, True, 128, 128, False)
    ).lower(*_qkv(shape, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# benchmarks/configs/mellum2-12b-a2.5b-train.json: one sequence of 8192,
# 8 query heads on 1 KV head (a group of 8), window 1024 or none; and
# train-2l-8k's 4 x 2048 with Mistral's 32 heads on 8
MELLUM_SHAPE = (1, 8192, 8, 1, 128)
MISTRAL_TRAIN_SHAPE = (4, 2048, 32, 8, 128)


@pytest.mark.parametrize("shape,window", [
    (TRAIN_SHAPE, 0), (MISTRAL_TRAIN_SHAPE, 0), (MELLUM_SHAPE, 0),
    (MELLUM_SHAPE, 1024)],
    ids=["bench_1b", "mistral-train", "mellum-full", "mellum-window"])
def test_flash_gradient_compiles_at_train_shapes(one_chip, shape, window):
    """The forward kernel (with the rows' log-sum-exp) and the two
    blockwise backward kernels, as a train step takes them; the names
    tell a window call from a full one."""
    def loss(q, k, v):
        # the blocks the train steps run with: `default_block`'s
        out = flash_attention(q, k, v, True, None, None, False, window)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        *_qkv(shape, one_chip)).compile()
    text = compiled.as_text()
    tail = "_window" if window else ""
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv"):
        assert name + tail in text
    assert text.count("tpu_custom_call") >= 3


# ------------------------------------------- the Laguna cell's kernels
# benchmarks/configs/laguna-s-2.1-serve.json: 32 lanes, 48 heads on a
# full layer and 72 on a sliding one over 8 KV heads (groups 6 and 9), a
# window of 512 (a table of 33 pages from `starts`), 128 of 256 experts
# of width 1024 at hidden 3072


# the full kind's table at every bucket to 8192 tokens, the window kind's
# at `min(bucket, 33)`
@pytest.mark.parametrize("heads,window,width", [
    (48, None, 4), (48, None, 16), (48, None, 64), (48, None, 256),
    (48, None, 512), (72, 512, 4), (72, 512, 16), (72, 512, 33)],
    ids=["full-group6-4", "full-group6-16", "full-group6-64",
         "full-group6-256", "full-group6", "window-group9-4",
         "window-group9-16", "window-group9"])
def test_paged_decode_compiles_at_laguna_shapes(one_chip, heads, window,
                                                width):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lanes = 32
    pages = 38 if window else 512
    slots = (1 + lanes * pages) * PAGE
    compiled = jax.jit(
        lambda q, k, v, bt, cl, st: paged_attention(
            q, k, v, bt, cl, page_size=PAGE, interpret=False,
            window=window, starts=st if window else None)
    ).lower(spec((lanes, 1, heads, D), jnp.bfloat16),
            spec((slots, HKV, D), jnp.bfloat16),
            spec((slots, HKV, D), jnp.bfloat16),
            spec((lanes, width), jnp.int32), spec((lanes,), jnp.int32),
            spec((lanes,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("paged_attention_decode_window" in text) == bool(window)
    assert "paged_attention_decode" in text


# the SDAR cell (benchmarks/configs/sdar-30b-a3b-chat-serve.json): 48 lanes
# of a block of 4 queries over 32 heads and 4 KV heads, so 32 query rows a
# KV head, at every table width its engine asks for (contexts to 4096)
@pytest.mark.parametrize("width", [4, 16, 64, 256])
def test_block_kernel_compiles_at_the_sdar_cells_shapes(one_chip, width):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lanes, block, hkv = 48, 4, 4
    slots = (1 + lanes * 256) * PAGE
    compiled = jax.jit(
        lambda q, k, v, bt, cl: paged_attention(
            q, k, v, bt, cl, page_size=PAGE, interpret=False)
    ).lower(spec((lanes, block, 32, D), jnp.bfloat16),
            spec((slots, hkv, D), jnp.bfloat16),
            spec((slots, hkv, D), jnp.bfloat16),
            spec((lanes, width), jnp.int32),
            spec((lanes,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_attention_block" in text
    assert "paged_attention_decode" not in text


@pytest.mark.parametrize("tokens", [192, 512, 2048],
                         ids=["blocks", "narrow", "wide"])
def test_expert_layer_compiles_at_the_sdar_cells_shapes(one_chip, tokens):
    """`ops.moe.moe_layer` as the cell runs it: 128 experts of width 768
    all held, 8 a token, for a block pass (48 lanes x 4 positions: 12
    rows an expert on average) and its prefill passes (2 x 256 and
    8 x 256: the cell's `prefill_chunk`)."""
    from ray_tpu.ops import moe

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda x, wr, w1, w3, w2, valid: moe.moe_layer(
            x, wr, w1, w3, w2, top_k=8, held=(0, 128), valid=valid,
            interpret=False)
    ).lower(spec((tokens, 2048), jnp.bfloat16),
            spec((2048, 128), jnp.bfloat16),
            spec((128, 2048, 768), jnp.bfloat16),
            spec((128, 2048, 768), jnp.bfloat16),
            spec((128, 768, 2048), jnp.bfloat16),
            spec((tokens,), jnp.bool_)).compile()
    assert compiled.as_text().count("moe_experts") >= 2


@pytest.mark.parametrize("tokens", [32, 512], ids=["decode", "prefill"])
def test_expert_layer_compiles_at_laguna_shapes(one_chip, tokens):
    """`ops.moe.moe_layer` for a decode batch (row tiles of 16) and a
    prefill pass (8 lanes x 64 tokens, tiles of 32): both grouped
    matmuls lower with an expert's whole matrices as one block."""
    from ray_tpu.ops import moe

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda x, wr, w1, w3, w2, valid: moe.moe_layer(
            x, wr, w1, w3, w2, top_k=10, held=(0, 128), valid=valid,
            interpret=False)
    ).lower(spec((tokens, 3072), jnp.bfloat16),
            spec((3072, 256), jnp.bfloat16),
            spec((128, 3072, 1024), jnp.bfloat16),
            spec((128, 3072, 1024), jnp.bfloat16),
            spec((128, 1024, 3072), jnp.bfloat16),
            spec((tokens,), jnp.bool_)).compile()
    assert compiled.as_text().count("moe_experts") >= 2


@pytest.mark.parametrize("rows", [4352, 512], ids=["prefill", "decode"])
@pytest.mark.parametrize("width,slices", [(7680, (4, 2)), (6144, (2, 1))],
                         ids=["pangu", "glm5"])
def test_sliced_expert_kernels_compile_at_the_latent_cells_shapes(
        one_chip, width, slices, rows):
    """`ops.moe.grouped_swiglu` where an expert's matrices do not fit one
    block — 16 held experts of `width` x 2048, a prefill pass's 272 row
    tiles of 16 and a decode pass's 32 — with the slices walked outside
    the row tiles: both calls lower inside the 96 MiB their limit is."""
    from ray_tpu.ops import moe

    assert (2048 // moe.hidden_tile(width, 2048, 2),
            width // moe.out_tile(width, 2048, 2)) == slices

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(
        lambda xs, w1, w3, w2, te, na: moe.grouped_swiglu(
            xs, w1, w3, w2, te, na, tm=16, interpret=False)
    ).lower(spec((rows, width), jnp.bfloat16),
            spec((16, width, 2048), jnp.bfloat16),
            spec((16, width, 2048), jnp.bfloat16),
            spec((16, 2048, width), jnp.bfloat16),
            spec((rows // 16,), jnp.int32),
            spec((), jnp.int32)).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2 and all("moe_experts" in c for c in calls)
    assert f"bf16[{rows},2048]" in calls[0] and f"f32[{rows},{width}]" \
        in calls[1]
    # the kernel compiler refuses a call over its limit: both passed it
    assert [_scoped_vmem(c) for c in calls] == [[96 * 1024 * 1024]] * 2


def test_expert_layer_gradient_compiles_at_mellum_shapes(one_chip):
    """`jax.grad` through `ops.moe.moe_layer` as the Mellum cell's step
    takes it: 8192 bfloat16 tokens of width 2304, 16 of 64 experts of
    width 896 held as float32 masters, top 8, row tiles of 128 — the
    forward's two calls and the backward's two, an expert's three float32
    gradient blocks resident in VMEM while its tiles last; and around
    them the row kernels (270 KB of assignments and 262 KB of routing
    weights as scalar operands, the combine's 8192 x 2304 float32 sums
    in VMEM), with no XLA gather over the 67,584 rows of the dropless
    buffer or the 65,536 assignments left."""
    from ray_tpu.ops import moe

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert moe.row_tile(8192, 8, 64) == 128

    def loss(x, wr, w1, w3, w2):
        y, _ = moe.moe_layer(x, wr, w1, w3, w2, top_k=8, held=(0, 16),
                             interpret=False)
        return jnp.sum(y)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        spec((8192, 2304), jnp.bfloat16), spec((2304, 64), jnp.float32),
        spec((16, 2304, 896), jnp.float32),
        spec((16, 2304, 896), jnp.float32),
        spec((16, 896, 2304), jnp.float32)).compile()
    text = compiled.as_text()
    assert "moe_experts_bwd_dx" in text and "moe_experts_bwd_dw" in text
    for name in ("moe_dispatch_rows", "moe_combine_rows",
                 "moe_combine_rows_bwd"):
        assert name in text, name
    wide = re.findall(r"\[(?:67584|8192,8),2304\]\S* gather\(", text)
    assert not wide, wide


# ------------------------------------- the latent-attention cell's kernel
# benchmarks/configs/openpangu-ultra-moe-718b-serve.json: 32 lanes of 128
# heads over ONE latent row a token (512 + 64 numbers in a pool 640
# wide), every table width to 8192 tokens


@pytest.mark.parametrize("width", [4, 16, 64, 256, 512])
def test_latent_decode_compiles_at_the_cells_shapes(one_chip, width):
    from ray_tpu.models.cache import latent_row_width
    from ray_tpu.ops.latent_attention import latent_paged_attention

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lanes, heads, row = 32, 128, latent_row_width(512 + 64)
    slots = (1 + lanes * (8192 // PAGE)) * PAGE
    compiled = jax.jit(
        lambda q, pool, bt, cl: latent_paged_attention(
            q, pool, bt, cl, page_size=PAGE, value_width=512,
            scale=192 ** -0.5, interpret=False)
    ).lower(spec((lanes, 1, heads, row), jnp.bfloat16),
            spec((slots, row), jnp.bfloat16),
            spec((lanes, width), jnp.int32),
            spec((lanes,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "latent_attention_decode" in text
    # a trace tells it from the key-and-value kernels, and the patterns
    # of their busy shares do not take it in
    assert "paged_attention_decode" not in text


# the same cell's prefill pass: 8 lanes (2 in the narrow pass) of 64
# queries x 128 heads over a context of every width bucket, the table
# read from the pass's `ctx`; a tile of the 8,192 query rows and a block
# of pages at a time, the scores and running sums in VMEM


@pytest.mark.parametrize("width", [256, 1024, 4096, 8192])
@pytest.mark.parametrize("lanes,chunk", [(8, 64), (2, 64), (2, 256)],
                         ids=["wide", "narrow", "deep"])
def test_latent_prefill_compiles_at_the_cells_shapes(one_chip, lanes, chunk,
                                                     width):
    from ray_tpu.models.cache import latent_row_width
    from ray_tpu.ops import latent_attention as la

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # (the deep pass's tile is 8 heads x 256 queries, the same 2,048 rows)
    assert la._prefill_tiles(128, chunk, width // PAGE, PAGE)[0] * chunk \
        == la._PREFILL_QUERY_ROWS
    heads, row = 128, latent_row_width(512 + 64)
    slots = (1 + 32 * (8192 // PAGE)) * PAGE
    compiled = jax.jit(
        lambda q, pool, ctx, ctx_pos, ctx_mask, q_pos:
        la.latent_chunk_attention(
            q, pool, ctx, ctx_pos, ctx_mask, q_pos, page_size=PAGE,
            value_width=512, scale=192 ** -0.5, interpret=False)
    ).lower(spec((lanes, chunk, heads, row), jnp.bfloat16),
            spec((slots, row), jnp.bfloat16),
            spec((lanes, width), jnp.int32),
            spec((lanes, width), jnp.int32),
            spec((lanes, width), jnp.bool_),
            spec((lanes, chunk), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "latent_attention_prefill" in text
    # no reader's pattern takes it for the decode kernel
    assert "latent_attention_decode" not in text
    assert "paged_attention_decode" not in text
    # the chip's compiler held the kernel's blocks, scratch and
    # temporaries to the VMEM the call asks for (it refuses what does
    # not fit), and the call asks for less than the chip has
    assert ('"scoped_memory_configs":[{"memory_space":"1","offset":"0",'
            f'"size":"{la._PREFILL_VMEM_BYTES}"}}]') in text
    assert la._PREFILL_VMEM_BYTES < 128 << 20


# ------------------------------------- the sparse-attention cell's kernels
# benchmarks/configs/glm-5-serve.json: 64 heads over a latent row of 512 +
# 64 (a pool 640 wide) and an index key of 128 scored by 32 index heads,
# 2,048 rows selected of contexts to 32,768; 16 decode lanes, 8 prefill
# lanes of 64 queries


def _glm_pools(lanes=16, positions=32768):
    from ray_tpu.models.cache import latent_row_width

    slots = (1 + lanes * (positions // PAGE)) * PAGE
    return slots, latent_row_width(512 + 64)


_INDEX_SHAPES = pytest.mark.parametrize("lanes,chunk,pages", [
    (8, 64, 256), (8, 64, 1024), (8, 64, 2048), (16, 1, 256), (16, 1, 2048),
    (2, 256, 64), (2, 256, 2048)],
    ids=["prefill-4096", "prefill-16384", "prefill-32768", "decode-256",
         "decode-2048", "deep-1024", "deep-32768"])


@pytest.fixture(scope="module")
def index_scores_text(one_chip):
    """The compiled text of `index_scores` at one of the cell's shapes,
    compiled once for the tests that read it."""
    import functools

    from ray_tpu.ops import sparse_index as si

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    @functools.cache
    def text(lanes, chunk, pages):
        slots, _row = _glm_pools()
        return jax.jit(
            lambda q, w, pool, table, lens, q_pos: si.index_scores(
                q, w, pool, table, lens, q_pos, page_size=PAGE,
                interpret=False)
        ).lower(spec((lanes, chunk, 32, 128), jnp.bfloat16),
                spec((lanes, chunk, 32), jnp.float32),
                spec((slots, 128), jnp.bfloat16),
                spec((lanes, pages), jnp.int32), spec((lanes,), jnp.int32),
                spec((lanes, chunk), jnp.int32)).compile().as_text()

    return text


def _scoped_vmem(text):
    """The scoped VMEM, in bytes, of every kernel of a compiled text."""
    import re

    return [int(n) for n in re.findall(
        r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"0",'
        r'"size":"(\d+)"', text)]


@_INDEX_SHAPES
def test_index_scores_compile_at_the_cells_shapes(index_scores_text, lanes,
                                                  chunk, pages):
    text = index_scores_text(lanes, chunk, pages)
    assert "tpu_custom_call" in text and "sparse_index_scores" in text
    # float32 scores of every position of the table's width
    assert f"f32[{lanes},{chunk},{pages * PAGE}]" in text


@_INDEX_SHAPES
def test_index_scores_read_the_pool_where_it_lies(index_scores_text, lanes,
                                                  chunk, pages):
    """The kernel walks the table itself: the program holds no copy of
    the pool relaid a page a row, none of the table's pages gathered,
    and the kernel's double buffer of pages fits the VMEM the module
    states."""
    from ray_tpu.ops import sparse_index as si

    text = index_scores_text(lanes, chunk, pages)
    slots, _row = _glm_pools()
    assert f"bf16[{slots // PAGE},{PAGE},128]" in text   # a page a tile
    for copy in (f"bf16[{slots // PAGE},{PAGE * 128}]",
                 f"bf16[{lanes * pages},",
                 f"bf16[{lanes},{pages * PAGE},128]"):
        assert copy not in text, copy
    sizes = _scoped_vmem(text)
    assert sizes and 0 < max(sizes) <= si._SCORE_VMEM_BYTES


@pytest.mark.parametrize("lanes,chunk,width", [
    (8, 64, 4096), (8, 64, 16384), (8, 64, 32768), (2, 256, 1024),
    (2, 256, 32768)],
    ids=["4096", "16384", "32768", "deep-1024", "deep-32768"])
def test_selecting_prefill_kernel_compiles_at_the_cells_shapes(
        one_chip, lanes, chunk, width):
    """The chunk kernel with a selection: the scores a block at a time
    beside the pages, thresholds and ties by query, at 64 heads — the
    wide pass's lanes of 64 queries and the deep pass's two of 256."""
    from ray_tpu.ops import latent_attention as la
    from ray_tpu.ops import sparse_index as si

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    heads = 64
    slots, row = _glm_pools()

    def call(q, pool, ctx, ctx_mask, q_pos, marks):
        return la.latent_chunk_attention(
            q, pool, ctx, None, ctx_mask, q_pos, page_size=PAGE,
            value_width=512, scale=256 ** -0.5, interpret=False,
            select=(marks, *si.select_threshold(marks, 2048)))

    compiled = jax.jit(call).lower(
        spec((lanes, chunk, heads, row), jnp.bfloat16),
        spec((slots, row), jnp.bfloat16), spec((lanes, width), jnp.int32),
        spec((lanes, width), jnp.bool_), spec((lanes, chunk), jnp.int32),
        spec((lanes, chunk, width), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "latent_attention_prefill" in text
    assert "latent_attention_decode" not in text
    # held to the VMEM the call asks for, less what the scores' buffer
    # and its semaphores take of it
    sizes = _scoped_vmem(text)
    assert sizes and 0 < max(sizes) <= la._PREFILL_VMEM_BYTES


def test_decode_over_gathered_rows_compiles_at_the_cells_shapes(one_chip):
    """16 lanes' 2,048 selected rows, gathered a lane after a lane, under
    an identity table of 128 pages a lane: the decode kernel at 64
    heads."""
    from ray_tpu.ops import latent_attention as la
    from ray_tpu.ops import sparse_index as si

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lanes, heads, k = 16, 64, 2048
    slots, row = _glm_pools()

    def call(q, pool, table, lens, marks):
        at = si.select_rows(marks, k)
        rows = si.gather_rows(pool, table, at, page_size=PAGE)
        return la.latent_paged_attention(
            q, rows, jnp.arange(lanes * k // PAGE, dtype=jnp.int32
                                ).reshape(lanes, k // PAGE),
            jnp.minimum(lens, k), page_size=PAGE, value_width=512,
            scale=256 ** -0.5, interpret=False)

    compiled = jax.jit(call).lower(
        spec((lanes, 1, heads, row), jnp.bfloat16),
        spec((slots, row), jnp.bfloat16), spec((lanes, 2048), jnp.int32),
        spec((lanes,), jnp.int32), spec((lanes, 32768), jnp.float32)
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "latent_attention_decode" in text
    assert f"bf16[{lanes * k},{row}]" in text      # the gathered pool


@pytest.mark.parametrize("pages", [256, 1024, 2048])
def test_decode_under_the_selections_mask_compiles_at_the_cells_shapes(
        one_chip, pages):
    """The three table buckets of the sparse cell past `index_topk` rows
    (models/pangu.py, `_selected`: no more pages than the selection has
    rows): 16 lanes' one query of 64 heads against the lane's own pages,
    the threshold search beside it.  No gathered pool, no sort; the
    table and the lengths in SMEM at the widest bucket; the kernel's
    double buffer, a lane's marks and the softmax's scratch inside the
    VMEM the call asks for."""
    from ray_tpu.ops import sparse_decode as sd
    from ray_tpu.ops import sparse_index as si

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lanes, heads, k = 16, 64, 2048
    slots, row = _glm_pools()

    def call(q, pool, table, lens, marks):
        return sd.latent_selected_attention(
            q, pool, table, lens, marks,
            *si.select_threshold(marks[:, 0], k), page_size=PAGE,
            value_width=512, scale=256 ** -0.5, interpret=False)

    compiled = jax.jit(call).lower(
        spec((lanes, 1, heads, row), jnp.bfloat16),
        spec((slots, row), jnp.bfloat16), spec((lanes, pages), jnp.int32),
        spec((lanes,), jnp.int32),
        spec((lanes, 1, pages * PAGE), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "latent_attention_decode_select" in text
    assert f"bf16[{lanes * k},{row}]" not in text    # no gathered pool
    assert " sort(" not in text and "sort." not in text
    assert f"bf16[{slots // PAGE},{PAGE},{row}]" in text  # a page a tile
    sizes = _scoped_vmem(text)
    assert sizes and 0 < max(sizes) <= sd._VMEM_BYTES
    # the program plans nothing of the pool's size beside the pool
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


# benchmarks/configs/granite-4.0-h-micro-serve.json: 64 lanes; a state
# pool of 65 slots of 64 heads x 64 x 128 float32 a state layer, updated
# in place by slot; 32 query heads of 64 over 8 KV heads, which the cache
# keeps in 4 pairs of 128 lanes (a 64-wide page copy is refused: "Slice
# shape along dimension 3 must be aligned to tiling (128), but is 64"),
# at the model's own scale of 1/64, every table width to 4096 tokens


def test_state_update_compiles_at_the_cells_shapes(one_chip):
    from ray_tpu.ops import ssm

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lanes, heads, p, n = 64, 64, 64, 128
    compiled = jax.jit(
        lambda pool, slots, x, dt, a, b, c, d: ssm.ssm_state_update(
            pool, slots, x, dt, a, b, c, d, interpret=False),
        donate_argnums=(0,)
    ).lower(spec((1 + lanes, heads, p, n), jnp.float32),
            spec((lanes,), jnp.int32), spec((lanes, heads, p), jnp.bfloat16),
            spec((lanes, heads), jnp.float32), spec((heads,), jnp.float32),
            spec((lanes, n), jnp.bfloat16), spec((lanes, n), jnp.bfloat16),
            spec((heads,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ssm_state_update" in text
    # in place: the pool that comes out IS the one that went in, and the
    # program holds no second copy of its 136 MB
    memory = compiled.memory_analysis()
    pool_bytes = (1 + lanes) * heads * p * n * 4
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes // 8


@pytest.mark.parametrize("width", [4, 16, 64, 256])
def test_paged_decode_compiles_at_granite_paired_shapes(one_chip, width):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lanes, heads, pairs, row = 64, 32, 4, 128
    slots = (1 + lanes * (4096 // PAGE)) * PAGE
    compiled = jax.jit(
        lambda q, k, v, bt, cl: paged_attention(
            q, k, v, bt, cl, page_size=PAGE, scale=1 / 64, interpret=False)
    ).lower(spec((lanes, 1, heads, row), jnp.bfloat16),
            spec((slots, pairs, row), jnp.bfloat16),
            spec((slots, pairs, row), jnp.bfloat16),
            spec((lanes, width), jnp.int32),
            spec((lanes,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_attention_decode" in text


# ------------------------------ the narrow and the deep prefill pass's program
# serve/llm.py, `_narrow_prefill_shape`: PREFILL_NARROW_LANES lanes of one
# chunk over the SECOND prefill width, 1024 columns in all three serving
# configurations, returning a token for each of the wide pass's 8 lanes;
# `_deep_prefill_shape`: as many lanes of the wide pass's slots, 256 a
# lane, here over the configuration's WIDEST context.  A whole step of
# the engine at the cell's depth and pools, by shapes alone (no weight
# is made; 6 to 15 s each): the bytes the compiler plans, nothing of
# results or times.

HBM_BYTES = int(15.75 * 2 ** 30)
DEEP_TEMP_BYTES = 2 ** 31


def _serving_config(name):
    import json
    import os

    from benchmarks.kinds import serve_glm, serve_laguna, serve_pangu
    from benchmarks.model_math import llama_kwargs

    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", name + ".json")
    with open(path) as f:
        cfg = json.load(f)
    model_kwargs = {"laguna": serve_laguna.model_kwargs,
                    "pangu_ultra_moe": serve_pangu.model_kwargs,
                    "glm_moe_dsa": serve_glm.model_kwargs}.get(
                        cfg["model_type"], llama_kwargs)
    return model_kwargs(cfg), cfg["deployment"]["engine"]["max_batch"]


@pytest.mark.parametrize("name", [
    "mistral-7b-v0.3-serve", "laguna-s-2.1-serve",
    "openpangu-ultra-moe-718b-serve", "glm-5-serve"])
@pytest.mark.parametrize("deep", [False, True], ids=["narrow", "deep"])
def test_narrow_prefill_program_compiles_at_the_cells_shapes(
        one_chip, monkeypatch, name, deep):
    import numpy as np

    import ray_tpu.ops
    from ray_tpu.models import cache as kv_cache, resolve
    from ray_tpu.serve.llm import (PREFILL_CHUNK, PREFILL_LANES,
                                   PREFILL_NARROW_LANES, _jitted_forward,
                                   _pow4_widths)

    # the kernels as the chip runs them, not the interpreter's programs
    monkeypatch.setattr(ray_tpu.ops, "kernel_mode", lambda: "compiled")
    model_kwargs, max_batch = _serving_config(name)
    family, cfg = resolve(model_kwargs)
    lanes, chunk = PREFILL_NARROW_LANES, PREFILL_CHUNK
    widths = _pow4_widths(4 * chunk, cfg.max_seq_len)
    width, far = widths[1], PREFILL_LANES * chunk // lanes
    assert (lanes, width, far) == (2, 1024, 256)
    if deep:
        chunk, width = far, widths[-1]
    model = family.build(cfg, PAGE)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def placed(tree):
        return jax.tree.map(lambda x: spec(x.shape, x.dtype), tree)

    params = placed(jax.eval_shape(
        model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)
    )["params"])
    # the engine's pools: every position of a full kind, of a window kind
    # the window and a chunk (`cache_groups.WindowPages`); a window kind gathers that
    # much context, in whole pages, where the pass's width is wider
    kinds = kv_cache.kinds_of(cfg.cache_spec())
    # (the pool's by the deep chunk, the pass's context by its own)
    span = {kind: -(-(window + far) // PAGE) if window
            else cfg.max_seq_len // PAGE for kind, window in kinds.items()}
    pools = placed(jax.eval_shape(lambda: kv_cache.make_pools(
        cfg.cache_spec(),
        {kind: (1 + max_batch * (pages + 2 * bool(kinds[kind]))) * PAGE
         for kind, pages in span.items()}, cfg.dtype)))
    groups = {}
    for kind, window in kinds.items():
        w = min(width, -(-(window + chunk) // PAGE) * PAGE) if window \
            else width
        groups[kind] = {"slots": spec((lanes, chunk), jnp.int32),
                        "ctx": spec((lanes, w), jnp.int32),
                        "ctx_pos": spec((lanes, w), jnp.int32),
                        "ctx_mask": spec((lanes, w), jnp.bool_)}
    lowered = _jitted_forward(0.0, 0).lower(
        model, params, pools, spec((lanes, chunk), jnp.int32),
        spec((lanes, chunk), jnp.int32), spec((PREFILL_LANES,), jnp.int32),
        spec((2,), jnp.uint32), groups, None)
    tok, _pools = lowered.out_info
    assert tok.shape == (PREFILL_LANES + len(getattr(model, "counters", ())),)
    mem = lowered.compile().memory_analysis()
    print(name, "deep" if deep else "narrow", width,
          mem.temp_size_in_bytes, mem.argument_size_in_bytes)
    # weights and pools as the cell holds them, and beside them what 2 x
    # 64 slots need: 38 to 56 MiB where the 8-lane pass plans 108 to 267
    # (the sparse configuration's 1024 columns take the dense path); the
    # deep pass's 512 slots at the widest context plan what the wide
    # pass's did there
    assert 0 < mem.temp_size_in_bytes < (DEEP_TEMP_BYTES if deep
                                         else 2 ** 27)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


# ------------------------------------------- the hybrid linear-attention cell
# benchmarks/configs/olmo-hybrid-7b-serve.json: 32 lanes; a state pool of
# 33 slots of 15 PAIRS of heads x 96 x 384 float32 a linear layer (30
# heads of 96 x 192: a pool of that shape is stored 256 lanes wide),
# updated in place by slot; prefill passes of 2 x 64, 8 x 64 and 2 x 256
# tokens through the chunk kernel; 30 KV heads of 128 in a cache row of
# 32 ("Slice shape along dimension 2 must be aligned to tiling (8), but
# is 30" is what a page's copy out of a 30-row pool gets), one query row
# a KV head, every table width to 16,384 tokens

OLMO = dict(lanes=32, heads=30, dk=96, dv=192, kv_rows=32)


def _spec(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.mark.parametrize("lanes,tokens", [(2, 64), (8, 64), (2, 256)],
                         ids=["narrow", "wide", "deep"])
def test_delta_chunk_kernel_compiles_at_the_cells_shapes(one_chip, lanes,
                                                         tokens):
    from ray_tpu.ops import delta_rule

    spec, f32 = _spec(one_chip), jnp.float32
    h, dk, dv = OLMO["heads"], OLMO["dk"], OLMO["dv"]
    compiled = jax.jit(
        lambda q, k, v, g, b, s0: delta_rule.gated_delta_chunk(
            q, k, v, g, b, s0, interpret=False)
    ).lower(spec((lanes, tokens, h, dk), f32),
            spec((lanes, tokens, h, dk), f32),
            spec((lanes, tokens, h, dv), jnp.bfloat16),
            spec((lanes, tokens, h), f32), spec((lanes, tokens, h), f32),
            spec((lanes, h // 2, dk, 2 * dv), f32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "gated_delta_chunk" in text


def test_delta_update_compiles_in_place_at_the_cells_shapes(one_chip):
    from ray_tpu.ops import delta_rule

    spec, f32 = _spec(one_chip), jnp.float32
    lanes, h, dk, dv = (OLMO[k] for k in ("lanes", "heads", "dk", "dv"))
    compiled = jax.jit(
        lambda pool, slots, q, k, v, g, b: delta_rule.gated_delta_update(
            pool, slots, q, k, v, g, b, interpret=False),
        donate_argnums=(0,)
    ).lower(spec((1 + lanes, h // 2, dk, 2 * dv), f32),
            spec((lanes,), jnp.int32), spec((lanes, h, dk), f32),
            spec((lanes, h, dk), f32), spec((lanes, h, dv), jnp.bfloat16),
            spec((lanes, h), f32), spec((lanes, h), f32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "gated_delta_update" in text
    # in place: the pool that comes out IS the one that went in, and the
    # program holds no second copy of its 73 MB
    memory = compiled.memory_analysis()
    pool_bytes = (1 + lanes) * h * dk * dv * 4
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes // 8


@pytest.mark.parametrize("width", [4, 16, 64, 256, 1024])
def test_paged_decode_compiles_at_a_row_of_32_heads(one_chip, width):
    """One query row a KV head (group 1), 32 rows of 128: a grid step
    holds 256 keys of them where a row of 8 heads holds 512
    (`pages_per_step`'s `row`), or the kernel's fast memory is refused
    by 48 KB."""
    from ray_tpu.ops.paged_attention import pages_per_step

    spec = _spec(one_chip)
    lanes, rows = OLMO["lanes"], OLMO["kv_rows"]
    slots = 4097 * PAGE
    compiled = jax.jit(
        lambda q, k, v, bt, cl: paged_attention(
            q, k, v, bt, cl, page_size=PAGE, interpret=False)
    ).lower(spec((lanes, 1, rows, D), jnp.bfloat16),
            spec((slots, rows, D), jnp.bfloat16),
            spec((slots, rows, D), jnp.bfloat16),
            spec((lanes, width), jnp.int32),
            spec((lanes,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_attention_decode" in text
    assert pages_per_step(width, PAGE, rows * D) == min(
        width, max(8, min(16, width // 4)))


@pytest.mark.parametrize("lanes,chunk,width", [
    (2, 64, 1024), (8, 64, 16384), (2, 256, 16384)],
    ids=["narrow", "wide", "deep"])
def test_paged_prefill_compiles_at_the_cells_shapes(one_chip, lanes, chunk,
                                                    width):
    """`paged_attention_prefill` (PR 54) at the cell's pass shapes — 2 x
    64 at the narrow program's one bucket, 8 x 64 and 2 x 256 over the
    widest table, 1,024 pages; 30 query heads over the row of 32 — holds
    its page buffers, the head-major block, the scores and the running
    sums in the VMEM it asks for; the program around it gathers no
    context and loops over no lanes (`cached_attention` under `lax.map`
    did both: a `while` whose body wrote bf16[16384,32,128] twice and
    planned 1.2 GB)."""
    from ray_tpu.ops import paged_prefill as pp

    spec = _spec(one_chip)
    heads, rows = OLMO["heads"], OLMO["kv_rows"]
    slots = 4097 * PAGE
    pool = spec((slots, rows, D), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, ctx, mask, q_pos: pp.paged_prefill_attention(
            q, k, v, ctx, mask, q_pos, page_size=PAGE, kv_heads=heads,
            interpret=False)
    ).lower(spec((lanes, chunk, heads, D), jnp.bfloat16), pool, pool,
            spec((lanes, width), jnp.int32), spec((lanes, width), jnp.bool_),
            spec((lanes, chunk), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_attention_prefill" in text
    assert "paged_attention_decode" not in text
    assert not re.search(r"\bwhile\(", text)
    assert f"[{width},{rows},{D}]" not in text
    assert ('"scoped_memory_configs":[{"memory_space":"1","offset":"0",'
            f'"size":"{pp._VMEM_BYTES}"}}]') in text
    assert pp._VMEM_BYTES < 128 << 20
    # beside the pools: the queries and the output head-major, nothing
    # that grows with the table
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 27
    # every head in one grid step, 512 keys a block at every bucket
    assert pp._tile_heads(heads, chunk) == heads
    assert pp._block_pages(pool, width // PAGE, PAGE) == 512 // PAGE


@pytest.mark.parametrize("width", [4, 16, 64, 256, 512, 2048])
@pytest.mark.parametrize("row", [64, 128, 512, 1024, 2048],
                         ids=lambda r: f"row-{r}")
def test_a_row_the_benchmark_had_keeps_its_pages_a_step(width, row):
    """Mistral's and Laguna's 8 heads of 128, granite's 4 pairs, SDAR's
    4 heads: no cache row of 2,048 numbers or fewer changes its grid, so
    their decode programs are what they were."""
    from ray_tpu.ops.paged_attention import pages_per_step

    assert pages_per_step(width, PAGE, row) \
        == min(width, max(8, min(32, width // 4)))


# ------------------------------------------------- the hybrid expert cell
# benchmarks/configs/qwen3-next-80b-a3b-serve.json: 128 lanes; a FLAT
# cache row of 2 KV heads x 256 (pools [slots, 512]: `[slots, 2, 256]` is
# stored sixteen heads tall), 8 query heads a KV head; 32,769 pages; a
# state pool of 129 slots of 16 PAIRS of value heads x 128 x 256 float32
# under 16 key heads (q and k arrive repeated to 32); 256 experts of
# 2048 x 512 held of a router 512 wide, 10 a token

QWEN = dict(lanes=128, heads=16, kv=2, d=256, value_heads=32, dk=128,
            dv=128, slots=32769 * PAGE)


@pytest.mark.parametrize("width", [4, 16, 64, 256, 1024])
def test_paged_decode_compiles_over_a_flat_row_of_two_wide_heads(one_chip,
                                                                 width):
    from ray_tpu.ops.paged_attention import pages_per_step

    spec = _spec(one_chip)
    lanes, row = QWEN["lanes"], QWEN["kv"] * QWEN["d"]
    pool = spec((QWEN["slots"], row), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, bt, cl: paged_attention(
            q, k, v, bt, cl, page_size=PAGE, interpret=False)
    ).lower(spec((lanes, 1, QWEN["heads"], QWEN["d"]), jnp.bfloat16),
            pool, pool, spec((lanes, width), jnp.int32),
            spec((lanes,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_attention_decode" in text
    # a row of 512 numbers keeps the grid every row of 2,048 or fewer has
    assert pages_per_step(width, PAGE, row) == min(
        width, max(8, min(32, width // 4)))
    # no copy of the pool beside it: a page is copied as it lies
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 24


@pytest.mark.parametrize("lanes,chunk,width", [
    (2, 64, 1024), (8, 64, 1024), (2, 256, 16384)],
    ids=["narrow", "wide", "deep"])
def test_paged_prefill_compiles_over_a_flat_row_of_two_wide_heads(
        one_chip, lanes, chunk, width):
    """8 query heads a KV head: a lane's chunk is 512 to 2,048 query rows
    a head, both heads in one grid step."""
    from ray_tpu.ops import paged_prefill as pp

    spec = _spec(one_chip)
    heads, kv, d = QWEN["heads"], QWEN["kv"], QWEN["d"]
    pool = spec((QWEN["slots"], kv * d), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, ctx, mask, q_pos: pp.paged_prefill_attention(
            q, k, v, ctx, mask, q_pos, page_size=PAGE, kv_heads=kv,
            interpret=False)
    ).lower(spec((lanes, chunk, heads, d), jnp.bfloat16), pool, pool,
            spec((lanes, width), jnp.int32), spec((lanes, width), jnp.bool_),
            spec((lanes, chunk), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_attention_prefill" in text
    assert not re.search(r"\bwhile\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 27
    assert pp._tile_heads(kv, chunk * heads // kv) == kv
    assert pp._block_pages(pool, width // PAGE, PAGE) == 512 // PAGE


@pytest.mark.parametrize("lanes,tokens", [(2, 64), (8, 64), (2, 256)],
                         ids=["narrow", "wide", "deep"])
def test_delta_chunk_kernel_compiles_at_the_second_head_shape(one_chip, lanes,
                                                              tokens):
    from ray_tpu.ops import delta_rule

    spec, f32 = _spec(one_chip), jnp.float32
    h, dk, dv = QWEN["value_heads"], QWEN["dk"], QWEN["dv"]
    compiled = jax.jit(
        lambda q, k, v, g, b, s0: delta_rule.gated_delta_chunk(
            q, k, v, g, b, s0, interpret=False)
    ).lower(spec((lanes, tokens, h, dk), f32),
            spec((lanes, tokens, h, dk), f32),
            spec((lanes, tokens, h, dv), jnp.bfloat16),
            spec((lanes, tokens, h), f32), spec((lanes, tokens, h), f32),
            spec((lanes, h // 2, dk, 2 * dv), f32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "gated_delta_chunk" in text


def test_delta_update_compiles_in_place_at_the_second_head_shape(one_chip):
    """16 pairs a lane in ONE grid step (`PAIR_BLOCK` does not divide
    them); the 271 MB pool is updated where it lies."""
    from ray_tpu.ops import delta_rule

    spec, f32 = _spec(one_chip), jnp.float32
    lanes, h, dk, dv = (QWEN[k] for k in ("lanes", "value_heads", "dk",
                                          "dv"))
    assert (h // 2) % delta_rule.PAIR_BLOCK
    compiled = jax.jit(
        lambda pool, slots, q, k, v, g, b: delta_rule.gated_delta_update(
            pool, slots, q, k, v, g, b, interpret=False),
        donate_argnums=(0,)
    ).lower(spec((1 + lanes, h // 2, dk, 2 * dv), f32),
            spec((lanes,), jnp.int32), spec((lanes, h, dk), f32),
            spec((lanes, h, dk), f32), spec((lanes, h, dv), jnp.bfloat16),
            spec((lanes, h), f32), spec((lanes, h), f32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "gated_delta_update" in text
    memory = compiled.memory_analysis()
    pool_bytes = (1 + lanes) * h * dk * dv * 4
    assert memory.alias_size_in_bytes >= pool_bytes
    # beside the pool: the lanes' k and q as COLUMNS, [lanes, 16, 128, 4]
    # float32, which the chip stores 128 lanes wide (134 MB at 128 lanes;
    # PERF.md section 7) — and no second copy of the pool
    assert memory.temp_size_in_bytes < 2 * pool_bytes


@pytest.mark.parametrize("tokens", [128, 512, 2048],
                         ids=["decode", "prefill", "four-passes"])
def test_expert_layer_compiles_at_the_hybrid_expert_cells_shapes(one_chip,
                                                                 tokens):
    """`ops.moe.moe_layer` as the cell runs it: 256 experts of 2048 x 512
    held of 512 routed over, 10 a token of which 5 land here: a decode
    pass of 128 lanes (2.5 rows an expert, tiles of 16) and a prefill
    pass's 512 tokens."""
    from ray_tpu.ops import moe

    spec = _spec(one_chip)
    compiled = jax.jit(
        lambda x, wr, w1, w3, w2, valid: moe.moe_layer(
            x, wr, w1, w3, w2, top_k=10, held=(0, 256), valid=valid,
            interpret=False)
    ).lower(spec((tokens, 2048), jnp.bfloat16),
            spec((2048, 512), jnp.bfloat16),
            spec((256, 2048, 512), jnp.bfloat16),
            spec((256, 2048, 512), jnp.bfloat16),
            spec((256, 512, 2048), jnp.bfloat16),
            spec((tokens,), jnp.bool_)).compile()
    assert compiled.as_text().count("moe_experts") >= 2
    assert moe.hidden_tile(2048, 512, 2) == 512
    assert moe.row_tile(tokens, 10, 512) == (16 if tokens < 1024 else 64)
