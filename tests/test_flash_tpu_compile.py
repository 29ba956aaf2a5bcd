"""The flash kernels compile for a v5e at the widths the models run.

No chip is attached: the TPU compiler installed here compiles for a chip
that is described (``jax.experimental.topologies``).  Interpret mode
cannot show what this shows: a slice Mosaic cannot tile, or a kernel
that asks for more scoped VMEM than it may have — the pair tiles hold
two heads' k/v and dk/dv where one head a program held one.  Nothing
runs, so this says nothing about results or times.
"""
import functools
import os
import re

import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attention as fa


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("B,S,H,D,causal,masked", [
    (4, 2048, 16, 64, True, False),     # gpt3-medium: head-folded fwd, fused bwd, pair tiles
    (2, 2048, 16, 128, True, False),    # gpt3-xl: q-grid fwd, fused bwd, one head a tile
    (2, 1024, 12, 64, True, False),     # gpt-125m: head-folded fwd and bwd
    (2, 512, 12, 64, False, True),      # BERT-base: the key bias
    # past the default 16 MB of scoped VMEM with two heads a tile (16.9 MB
    # once blocks are double-buffered: more than one tile or batch row)
    (2, 4096, 16, 64, True, False),
    (2, 8192, 16, 64, True, False),     # the fused backward at its S*D cap
    (1, 16384, 2, 64, True, False),     # two-pass backward: a grid of one tile, lowering only
    (2, 16384, 16, 64, True, False),    # two-pass backward, pair tiles, blocks double-buffered
    (2, 768, 12, 64, True, False),      # the backward's 256 block: 512 does not divide S
    (2, 2048, 8, 32, True, False),      # four heads a tile
    (2, 2048, 12, 96, True, False),     # transposed: D=96
    (2, 2048, 1, 64, True, False),      # transposed: a single head of 64
], ids=["medium", "xl", "gpt125m", "bert_mask", "s4096", "fused_cap",
        "two_pass", "two_pass_pairs", "s768", "d32", "d96_transposed",
        "h1_transposed"])
def test_kernels_compile_for_v5e(one_chip, B, S, H, D, causal, masked):
    sds = functools.partial(_sds, one_chip)
    x = sds((B, S, H, D))
    bias = (sds((B, S), jnp.float32),) if masked else ()
    # conftest asks for "highest" everywhere; the chip's programs run at
    # the default, and Mosaic takes bf16 operands at no other
    with jax.default_matmul_precision(None):
        fwd = jax.jit(lambda q, k, v, *b: fa.flash_attention_fwd_lse(
            q, k, v, *b, causal=causal)).lower(x, x, x, *bias).compile()
        bwd = jax.jit(lambda q, k, v, o, lse, g, *b: fa.flash_attention_bwd(
            q, k, v, o, lse, g, *b, causal=causal)).lower(
                x, x, x, x, sds((B, H, S), jnp.float32), x, *bias).compile()
    assert "tpu_custom_call" in fwd.as_text()
    assert "tpu_custom_call" in bwd.as_text()


@pytest.mark.parametrize("block_q,block_k", [(256, 256), (512, 256),
                                             (256, 512), (1024, 512)])
@pytest.mark.parametrize("entry", ["_flash_bhsd_bwd_fused", "_flash_bhsd_bwd"])
def test_backward_blocks_compile_for_v5e(one_chip, entry, block_q, block_k):
    """The q-grid backward rungs at the fit cell's shape with the blocks a
    sweep passes: the static schedule (blocks that nest on the diagonal)
    and the block masked whole where the diagonal's place moves with the
    grid (a traced offset in the mask) both lower."""
    B, S, H, D = 4, 2048, 16, 64
    sds = functools.partial(_sds, one_chip)
    x = sds((B, S, H * D))
    with jax.default_matmul_precision(None):
        bwd = jax.jit(lambda q, k, v, o, lse, g: getattr(fa, entry)(
            q, k, v, o, lse, g, head_dim=D, causal=True, block_q=block_q,
            block_k=block_k)).lower(
                x, x, x, x, sds((B, H, S), jnp.float32), x).compile()
    assert "tpu_custom_call" in bwd.as_text()


def test_the_kernels_ask_for_no_more_vmem():
    """``_params`` is the parent's: 16 MB of scoped VMEM a head of the
    tile (at most six), the compiler's default for a tile of one head."""
    mb = 1024 * 1024
    assert fa._params(128, 64).vmem_limit_bytes == 32 * mb
    assert fa._params(128, 32).vmem_limit_bytes == 64 * mb
    assert fa._params(128, 16).vmem_limit_bytes == 96 * mb
    assert fa._params(128, 128).vmem_limit_bytes is None
    assert fa._params(64, 64).vmem_limit_bytes is None


@pytest.mark.parametrize("S", [1024, 2048, 4096])
def test_latent_prefill_attention_compiles_for_v5e(one_chip, S):
    """The expanded latent attention's call: 64 heads, q/k of 192 and v of
    128 zero-padded to 256 (models/mla_moe.py), one prompt, no LSE."""
    x = _sds(one_chip, (1, S, 64, 256))
    with jax.default_matmul_precision(None):
        fwd = jax.jit(lambda q, k, v: fa.flash_attention_fwd(
            q, k, v, causal=True)).lower(x, x, x).compile()
    assert "tpu_custom_call" in fwd.as_text()


@pytest.mark.parametrize("rows,k,n", [
    (64, 4096, 4096), (64, 2048, 4096),            # a decode step: 8 x 8 pairs
    (32768, 4096, 4096), (32768, 2048, 4096),      # a 4,096 bucket, worst case
    (4096, 4096, 4096)])                           # a 512 bucket
def test_grouped_matmul_compiles_for_v5e(one_chip, rows, k, n):
    """The expert layer's grouped matmul (megablox) at the widths of
    sarvam-105b: 32 held experts, gate+up 4096 -> 4096, down 2048 -> 4096."""
    from paddle_tpu.ops import grouped_matmul as gm
    lhs, rhs = _sds(one_chip, (rows, k)), _sds(one_chip, (32, k, n))
    sizes = _sds(one_chip, (32,), jnp.int32)
    with jax.default_matmul_precision(None):
        out = jax.jit(lambda a, b, s: gm._expert_grouped_matmul(
            a, b, s, impl="megablox")).lower(lhs, rhs, sizes).compile()
    assert "tpu_custom_call" in out.as_text()


@pytest.mark.parametrize("nH,nKV,pool,dtype", [
    (16, 16, 1025, jnp.bfloat16),       # chat-gpt3-xl: 8 slots of 2,048 in pages of 16
    (32, 8, 1025, jnp.bfloat16),        # llama, grouped: four query heads a kv head
    (16, 16, 1025, jnp.float32),        # an engine left at float32
], ids=["chat_cell", "llama_grouped", "float32"])
def test_paged_attention_compiles_for_v5e(one_chip, nH, nKV, pool, dtype):
    """The paged decode kernel at the chat cell's real shape (8 slots, 128
    table entries, heads of 128, the pools whole in HBM) within the
    default scoped VMEM: it asks for no limit of its own."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    sds = functools.partial(_sds, one_chip)
    pages = sds((pool, 16, nKV, 128), dtype)
    with jax.default_matmul_precision(None):
        out = jax.jit(pa.paged_attention).lower(
            sds((8, nH, 128), dtype), pages, pages,
            sds((8, 128), jnp.int32), sds((8,), jnp.int32)).compile()
    text = out.as_text()
    assert "tpu_custom_call" in text
    scoped = [int(n) for n in re.findall(
        r'scoped_memory_configs":\[\{"memory_space":"1","offset":"0",'
        r'"size":"(\d+)"', text)]
    assert scoped and max(scoped) < 4 * 1024 * 1024   # 16 MB is the default
    # the pools go to the kernel where they lie: no copy of a pool
    assert f"[{pool},16,{nKV},128]" not in "".join(
        l for l in text.splitlines() if " copy(" in l)
