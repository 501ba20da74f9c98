"""The main path's kernels compiled for a described v5e chip, at the
published widths: what interpret mode cannot show (tile alignment, VMEM,
Mosaic's own refusals). Nothing runs and no chip is attached; the topology
is described inside a fixture, so collection is the same in every worker,
and all such compiles live in this one file (the on-chip-measurement
guide, section 2)."""

import functools
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.mark.parametrize("seq, heads, head_dim, dtype, blocks", [
    (8192, (32, 8), 64, "bfloat16", (512, 512)),
    (2047, (32, 8), 64, "bfloat16", (128, 128)),
    # no block argument, as the decoder and zoo/transformer.py call it:
    # the shapes pallas_ops.tile_shapes derives
    (8192, (32, 8), 64, "bfloat16", None),       # the LM cell's
    (2047, (32, 8), 64, "bfloat16", None),       # PR 21's awkward length
    (2048, (16, 16), 128, "bfloat16", None),     # a head as wide as a lane
    (2048, (16, 4), 128, "float32", None),       # the most VMEM a tile asks
    # lengths under and between tile multiples: one tile of the padded
    # length (640, 384), whose lse block Mosaic takes only lane-aligned
    (520, (32, 8), 64, "bfloat16", None),
    (300, (8, 8), 64, "bfloat16", None),
    # the nemotron_h cell's: a group of 16 query heads a K/V head, a head
    # as wide as a lane
    (8192, (32, 2), 128, "bfloat16", None),
    # latent attention's: queries and keys 192 wide (no multiple of the
    # lane tile), values 128, the joyai-llm-flash-ep32 cell's and a float32
    # pair at the most VMEM such a tile asks
    (8192, (32, 32), (192, 128), "bfloat16", None),
    (2048, (4, 4), (192, 128), "float32", None),
    (300, (4, 4), (192, 128), "bfloat16", None),
])
def test_flash_attention_with_grouped_queries_compiles_for_the_v5e(
        one_chip, no_compile_cache, seq, heads, head_dim, dtype, blocks):
    """LFM2-8B-A1B's attention: 32 query heads over 8 key/value heads of
    64, bfloat16, forward and the backward kernel, at 8,192 tokens in
    512 x 512 tiles and at an awkward length (PR 21's S = 2,047); and
    the tile shapes the kernels derive themselves, so that a derived
    shape that outgrows VMEM, or a tile body Mosaic refuses, fails here
    and not on the chip."""
    import jax
    import jax.numpy as jnp

    from tpudl.pallas_ops import flash_attention

    kw = {} if blocks is None else dict(block_q=blocks[0],
                                        block_k=blocks[1])

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False, **kw)
        return jnp.sum(out.astype(jnp.float32))

    d_qk, d_v = head_dim if isinstance(head_dim, tuple) else (head_dim,) * 2
    q = jax.ShapeDtypeStruct((1, seq, heads[0], d_qk), dtype,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, seq, heads[1], d_qk), dtype,
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, seq, heads[1], d_v), dtype,
                             sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        q, k, v).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2      # forward, backward
    grads = compiled.output_shardings  # compiled: shapes came through
    assert grads is not None


@pytest.mark.parametrize("seq, heads, dtype", [
    (8192, (32, 32), "bfloat16"),    # the joyai-llm-flash-ep32 cell's
    (2048, (4, 4), "float32"),       # the most VMEM such a tile asks
    (300, (8, 2), "bfloat16"),       # a padded tile; four heads a step
])
def test_a_shared_key_and_head_major_operands_compile_for_the_v5e(
        one_chip, no_compile_cache, seq, heads, dtype):
    """Latent attention's operands as ``mla_op`` hands them over: ``q``
    192 wide and head-major, a 128-wide key a head, the ONE 64-wide
    rotated key of a batch entry beside it (joined to the head's tile in
    VMEM, its cotangent split off there), 128-wide values; two batch
    entries, so the shared tile's row is not the head's."""
    import jax
    import jax.numpy as jnp

    from tpudl.pallas_ops import flash_attention

    def loss(q, k, k_shared, v):
        out, lse = flash_attention(q, k, v, causal=True, interpret=False,
                                   layout="bhsd", k_shared=k_shared,
                                   return_lse=True)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)

    def operand(*shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3))).lower(
        operand(2, heads[0], seq, 192), operand(2, heads[1], seq, 128),
        operand(2, seq, 64), operand(2, heads[1], seq, 128)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2


@pytest.mark.parametrize("saved, kernels", [(True, 2), (False, 3)])
def test_a_rematerialised_layer_compiles_to_two_kernels_for_the_v5e(
        one_chip, no_compile_cache, saved, kernels):
    """What the decoder's policy is for, in the chip's own program: under
    ``jax.checkpoint`` with the forward's output and row statistics
    saved, the compiled gradient of a projection, the kernels and
    ``W_o`` holds forward and backward; under the routes-only policy it
    holds a second forward."""
    import jax
    import jax.numpy as jnp

    from tpudl import pallas_ops
    from tpudl.zoo import moe

    names = (moe.ROUTES, pallas_ops.SAVED) if saved else (moe.ROUTES,)

    @functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(*names))
    def layer(x, w_in, w_out):
        q, k, v = jnp.split((x @ w_in).reshape(1, 2048, 12, 128), 3, axis=2)
        out = pallas_ops.flash_attention(q, k, v, causal=True,
                                         interpret=False)
        return x + out.reshape(1, 2048, 512) @ w_out

    x = jax.ShapeDtypeStruct((1, 2048, 512), "bfloat16", sharding=one_chip)
    w_in = jax.ShapeDtypeStruct((512, 1536), "bfloat16", sharding=one_chip)
    w_out = jax.ShapeDtypeStruct((512, 512), "bfloat16", sharding=one_chip)
    # the value keeps the first forward alive, as the next layer does
    text = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(layer(*a).astype(jnp.float32)),
        (0, 1, 2))).lower(x, w_in, w_out).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == kernels


@pytest.mark.parametrize("seq, heads, groups, width, chunk, held", [
    # the nemotron-twotower-30b-a3b-ep16 cell's mixer: 64 heads of 64 in 8
    # groups, state 128, chunks of 128, eight of them a grid step
    (8192, 64, 8, 64, 128, "float32"),
    # the control's: the recurrence held in bfloat16 still lowers
    (8192, 64, 8, 64, 128, "bfloat16"),
    (1000, 64, 8, 64, 128, "float32"),      # padded: 8 chunks for 7.8
    (2048, 16, 8, 128, 256, "float32"),     # a head a lane tile, long chunks
])
def test_the_selective_scan_compiles_for_the_v5e(
        one_chip, no_compile_cache, seq, heads, groups, width, chunk, held):
    """``pallas_ops.ssd_scan`` with bfloat16 operands, forward and
    gradient: the forward as called, the forward that writes the chunk
    states, and the backward, at the tiles the shapes derive. What the
    interpreter cannot show: lane slices at a chunk's offset inside a
    grid step's block, the ``[R, Q]`` transposes, the products that
    contract over rows, VMEM at eight chunks a step."""
    import jax
    import jax.numpy as jnp

    from tpudl.pallas_ops import ssd_scan

    def loss(x, dt, a, b, c):
        return jnp.sum(ssd_scan(x, dt, a, b, c, chunk=chunk,
                                interpret=False) ** 2)

    def operand(shape, dtype="bfloat16"):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3, 4))).lower(
        operand((seq, heads, width)), operand((seq, heads), "float32"),
        operand((heads,), held), operand((seq, groups, 128)),
        operand((seq, groups, 128))).compile()
    text = compiled.as_text()
    # the value comes from the states-saving forward too: XLA keeps one
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # nothing of [chunks, H, Q, Q] in HBM: the largest temporaries are y,
    # its cotangent (float32) and the chunk states (bfloat16)
    pad = -seq % chunk
    assert compiled.memory_analysis().temp_size_in_bytes < (
        (seq + pad) * heads * width * (4 + 4) * 1.1
        + (seq + pad) // chunk * heads * width * 128 * 2 * 1.1)


@pytest.mark.parametrize("m, k, n, gated", [
    (65536, 2048, 768, True),      # joyai-llm-flash-ep32, a sequence
    (196608, 2816, 2048, False),   # nemotron-twotower, relu2
    (131072, 2048, 1792, True),    # lfm2-8b-a1b
])
def test_the_grouped_products_compile_for_the_v5e(
        one_chip, no_compile_cache, m, k, n, gated):
    """``pallas_ops.grouped_dot`` as a routed layer calls it, at the LM
    cells' shapes with bfloat16 operands: the gate and up-projection as
    one call, the down-projection, and the gradient of every operand.
    What the interpreter cannot show: the grid's length as a value on the
    device, whole K and N blocks in VMEM, the weights read transposed,
    the product that contracts over rows."""
    import jax
    import jax.numpy as jnp

    from tpudl.pallas_ops import grouped_dot

    def loss(x, ws, w2, sizes):
        outs = grouped_dot(x, ws, sizes, interpret=False)
        h = outs[0] * outs[-1]
        y = grouped_dot(h, w2, sizes, interpret=False)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def operand(shape, dtype="bfloat16"):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ws = tuple(operand((8, k, n)) for _ in range(2 if gated else 1))
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        operand((m, k)), ws, operand((8, n, k)),
        operand((8,), "int32")).compile()
    # two forward kernels; the rows' cotangent of each call; one weights'
    # kernel a right-hand side
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2 + 2 + len(ws) + 1
