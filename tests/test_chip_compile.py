"""The main path's kernels, compiled for the chip that is not attached.

The TPU's compiler is installed beside the CPU backend and compiles for a
chip that is described (``v5e:2x2``), so what it refuses (a misaligned
slice, too much VMEM, a program that does not fit the device) is found here
at no chip time.  Nothing runs: these tests say nothing about results or
times.

Only one process may load the TPU's library, so the topology is described
inside a fixture of this one file and nowhere at import time: every xdist
worker then collects the same tests and only the worker that runs this file
loads the library.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.ops.pallas_ops import _flash_attention_pallas
from mxnet_tpu.serving.decode import (DecodeEngine, ShardedDecodeModel,
                                      TinyCausalLM)

HBM_BYTES = 16 * 2 ** 30      # one v5e chip
# the seventh cell's scan, forward and backward, compiled with each head's
# products its own: sharing them across a group's heads may keep no more
SCAN_TEMP_BYTES = 637985792

# chip_smoke.py's serve phase: the width a chip is built for
GEOM = dict(vocab_size=50304, hidden=2048, num_layers=16, max_len=4096)
HEADS, SLOTS, BLOCK, CHUNK = 16, 8, 16, 128
WIDTH = DecodeEngine.worst_case_width(512, 48, BLOCK)
POOL = (GEOM["num_layers"], SLOTS * WIDTH + 1, BLOCK, HEADS,
        GEOM["hidden"] // HEADS)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # an executable compiled for a described chip can be written to the
    # persistent cache but not read back without a chip
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _model():
    """TinyCausalLM of GEOM whose parameters are shapes, not arrays."""
    shapes = TinyCausalLM.param_shapes(**GEOM)
    handles = {k: NDArray(jax.ShapeDtypeStruct(v, jnp.float32))
               for k, v in shapes.items()}
    return TinyCausalLM(num_heads=HEADS, params=handles, **GEOM), shapes


def _fits(compiled):
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used < HBM_BYTES, "%.2f GB on a 16 GB chip" % (used / 2 ** 30)
    return used


@pytest.mark.parametrize("shape,dtype", [
    ((8, 12, 1024, 64), jnp.bfloat16),
    ((2, 16, 4096, 128), jnp.bfloat16),
    ((2, 12, 1000, 64), jnp.bfloat16),      # ragged: padded and masked
    # K/V stream through the grid one block at a time, so VMEM use does
    # not grow with the sequence: with a whole (Tk, D) row of K and of V
    # resident per program this shape was refused (16.25M of 16.00M VMEM)
    ((1, 16, 8192, 128), jnp.float32),
])
def test_flash_attention_compiles_for_v5e(one_chip, shape, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    scale = 1.0 / np.sqrt(shape[-1])
    compiled = jax.jit(lambda q, k, v: _flash_attention_pallas(
        q, k, v, True, scale)).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("kernel", ["decode", "chunk_prefill"])
def test_decode_kernels_compile_for_v5e(one_chip, kernel):
    model, shapes = _model()

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {k: s(v) for k, v in shapes.items()}
    i32 = jnp.int32
    if kernel == "decode":
        small = (s((SLOTS,), i32), s((SLOTS,), i32), s((SLOTS, WIDTH), i32))
        fn = model.decode_fn
    else:
        small = (s((1, CHUNK), i32), s((1,), i32), s((1,), i32),
                 s((1, WIDTH), i32))
        fn = model.chunk_prefill_fn
    compiled = jax.jit(fn).lower(params, *small, s(POOL), s(POOL)).compile()
    # weights + both pools in, logits + both pools out (pools are not
    # donated: ROADMAP S1), and all of it fits one chip
    used = _fits(compiled)
    weights = 4 * sum(int(np.prod(v)) for v in shapes.values())
    assert used > weights + 4 * 4 * int(np.prod(POOL))


def test_sharded_decode_step_compiles_for_four_chips(topo, monkeypatch):
    """The tp=4 decode step over a mesh of the described devices: each
    device holds a quarter of the weights (all but nothing of the logits)
    and a quarter of each pool."""
    model, shapes = _model()
    # nothing can be placed on a described device: the wrapper's own
    # device_put of the weights passes the shapes through
    monkeypatch.setattr(jax, "device_put", lambda x, sharding: x)
    sharded = ShardedDecodeModel(model, tp=4, devices=list(topo.devices)[:4])
    monkeypatch.undo()
    assert sharded.mesh.devices.size == 4

    def s(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(sharded.mesh, spec))

    specs = model.partition_specs()
    params = {k: s(v, jnp.float32, specs[k]) for k, v in shapes.items()}
    pool = s(POOL, jnp.float32, P(None, None, None, "tp"))
    i32 = jnp.int32
    compiled = jax.jit(sharded.decode_fn).lower(
        params, s((SLOTS,), i32, P()), s((SLOTS,), i32, P()),
        s((SLOTS, WIDTH), i32, P()), pool, pool).compile()
    text = compiled.as_text()
    assert "all-reduce" in text and "all-gather" not in text
    # memory_analysis() counts one device of the mesh
    per_device = _fits(compiled)
    weights = 4 * sum(int(np.prod(v)) for v in shapes.values())
    pools = 2 * 4 * int(np.prod(POOL))
    assert per_device < (weights + 2 * pools) / 4 * 1.25


# -- the block-diffusion cell's kernels at its published widths --------------

def _pallas_grids(jaxpr):
    """{name: grid} of the ``pallas_call``s a jaxpr holds, at any depth."""
    grids = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids[eqn.params["name"]] = tuple(eqn.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            grids.update(_pallas_grids(sub))
    return grids


def test_block_mask_attention_compiles_for_v5e_forward_and_backward(one_chip):
    """32 query heads to 4 of 128 over 8,192 [noised; clean] rows, float32
    in: the forward kernel and the one backward kernel, tiles of 512 x 512,
    with one key/value head's dk and dv (4 MiB each) held in VMEM, which
    takes more than the 16 MiB a kernel has by default.  The grid walks the
    flat list: 80 visited tiles a head, where 16 query tiles padded to the
    longest row's 9 were 144."""
    from mxnet_tpu.ops.pallas_ops import block_mask_attention
    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.float32,
                              sharding=one_chip)
    step = jax.grad(lambda q, k, v: jnp.sum(block_mask_attention(
        q, k, v, 4096, 4, interpret=False)), (0, 1, 2))
    compiled = jax.jit(step).lower(q, kv, kv).compile()
    text = compiled.as_text()
    # the custom calls carry the pallas_calls' names: %attention_bwd.1 = ...
    assert set(re.findall(r"%(attention_\w+?)(?:\.\d+)? = ", text)) == {
        "attention_fwd", "attention_bwd"}
    assert _pallas_grids(jax.make_jaxpr(step)(q, kv, kv).jaxpr) == {
        "attention_fwd": (32, 80), "attention_bwd": (32, 80)}
    _fits(compiled)


def test_sparse_attention_compiles_for_v5e_forward_and_backward(one_chip):
    """The same two kernels under a data mask at the sparse cell's size: 32
    query heads to 4 of 128 over 8,192 rows, float32 in, tiles of 512 x 512,
    the picked pairs an int8 (8192, 8192) operand streamed by tile (and its
    transpose for the backward kernel) over the causal mask's tables; with
    them the selection's kernel over rows of 8,192 float32
    scores (its 45 passes in VMEM) and the kernel that averages the
    attention's distribution over the 32 heads.  The three that walk the
    causal tiles take a grid step for each of the 136 and none besides."""
    from mxnet_tpu.ops.decoder_ops import select_top_k
    from mxnet_tpu.ops.pallas_ops import (head_mean_probabilities,
                                          sparse_attention)
    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.float32,
                              sharding=one_chip)

    scores = jax.ShapeDtypeStruct((1, 8192, 8192), jnp.float32,
                                  sharding=one_chip)

    def loss(q, k, v, scores):
        # scoped as the operators scope them: a kernel's operation is named
        # after the innermost component of its name stack
        with jax.named_scope("dsa.select"):
            pairs = select_top_k(scores, 2048, interpret=False)
        out, lse = sparse_attention(q, k, v, pairs, interpret=False)
        with jax.named_scope("dsa.index_loss"):
            target = head_mean_probabilities(q, k, lse, pairs,
                                             interpret=False)
        return jnp.sum(out) + jnp.sum(jnp.where(pairs != 0, target, 0.0))

    step = jax.value_and_grad(loss, (0, 1, 2))
    compiled = jax.jit(step).lower(q, kv, kv, scores).compile()
    assert set(re.findall(r"%((?:attention|index)_\w+?)(?:\.\d+)? = ",
                          compiled.as_text())) == {
        "attention_fwd", "attention_bwd", "index_select", "index_target"}
    assert _pallas_grids(jax.make_jaxpr(step)(q, kv, kv, scores).jaxpr) == {
        "attention_fwd": (32, 136), "attention_bwd": (32, 136),
        "index_target": (1, 136, 32), "index_select": (1, 128)}
    _fits(compiled)


@pytest.mark.parametrize("rows,total,held,width,picked,slots,hidden,blocks", [
    (8192, 128, 16, 768, 8, 69632, 2048, 1),   # the third and fourth cells'
    (16384, 64, 8, 1536, 4, 67584, 2048, 2),   # the fifth cell's
    (16384, 64, 8, 896, 8, 133120, 2304, 7),   # the sixth cell's
])
def test_held_experts_layer_compiles_for_v5e(one_chip, monkeypatch, rows,
                                             total, held, width, picked,
                                             slots, hidden, blocks):
    """A chip's held experts of ``width`` x ``hidden``, ``picked`` per token,
    forward and backward, recomputed as the decoder cells' layers are: the
    pairs in ``rows * min(picked, held)`` slots and a tile of 256 an expert,
    four kernels over whole tiles with an expert's matrices in VMEM; a grid
    step a tile (and, for the weights' gradients, a block of the largest
    multiple of 128 up to 768 hidden units that divides the width: 128 of
    896), whatever the router picks.  Each row's slots are added up by
    ``slot_combine``, a grid step a tile of 120 rows and a held expert, for
    the output and for the rows' gradient and not in the recomputed pass,
    whose output nothing reads."""
    from mxnet_tpu.ops.pallas_ops import ATTENTION_RESIDUALS
    from mxnet_tpu.parallel.moe import moe_held_apply
    # the layer asks the backend whether its kernels can run: here the CPU's
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    policy = jax.checkpoint_policies.save_only_these_names(
        *ATTENTION_RESIDUALS)

    def loss(x, router_w, gate_w, up_w, down_w):
        out, load = jax.checkpoint(lambda *a: moe_held_apply(*a, picked),
                                   policy=policy)(
            x, router_w, gate_w, up_w, down_w)
        return jnp.sum(out) + load[1]

    # the value too: a pass that needs no output drops the down projection
    step = jax.value_and_grad(loss, (0, 1, 2, 3, 4))
    shapes = (s(rows, hidden), s(total, hidden), s(held, width, hidden),
              s(held, width, hidden), s(held, hidden, width))
    compiled = jax.jit(step).lower(*shapes).compile()
    text = compiled.as_text()
    assert set(re.findall(r"%(moe_\w+?)(?:\.\d+)? = ", text)) == {
        "moe_experts_hidden", "moe_experts_down", "moe_experts_bwd",
        "moe_experts_wgrad"}
    assert len(re.findall(r"%slot_combine(?:\.\d+)? = ", text)) == 2
    # the recomputed pass runs the hidden units' kernel again, and no other
    assert len(re.findall(r"%moe_experts_hidden(?:\.\d+)? = ", text)) == 2
    assert len(re.findall(r"%moe_experts_down(?:\.\d+)? = ", text)) == 1
    tiles = slots // 256
    assert _pallas_grids(jax.make_jaxpr(step)(*shapes).jaxpr) == {
        "moe_experts_hidden": (tiles,), "moe_experts_down": (tiles,),
        "moe_experts_bwd": (tiles,),
        "moe_experts_wgrad": (blocks, tiles),
        "slot_combine": (-(-rows // 120), held)}
    _fits(compiled)


def test_relu2_experts_layer_compiles_for_v5e(one_chip, monkeypatch):
    """The seventh cell's held experts: 8 of 128 relu² experts of 1,856 x
    2,688, 6 picked per token, over 8,192 rows, forward and backward,
    recomputed as its layers are.  The same four kernels run with two
    matrices at the width as it is, an expert's matrices whole: 49,152 slots
    and a tile of 256 an expert (200 tiles), blocks of 640 hidden units for
    the weights' gradients, the last ragged (576)."""
    from mxnet_tpu.ops.pallas_ops import ATTENTION_RESIDUALS
    from mxnet_tpu.parallel.moe import moe_held_apply
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    policy = jax.checkpoint_policies.save_only_these_names(
        *ATTENTION_RESIDUALS)

    def loss(x, router_w, up_w, down_w, bias):
        out, load = jax.checkpoint(lambda *a: moe_held_apply(
            a[0], a[1], None, a[2], a[3], 6, scoring="sigmoid", scale=2.5,
            bias=a[4], act="relu2", eps=1e-20), policy=policy)(
            x, router_w, up_w, down_w, bias)
        return jnp.sum(out) + load[1]

    step = jax.value_and_grad(loss, (0, 1, 2, 3))
    shapes = (s(8192, 2688), s(128, 2688), s(8, 1856, 2688),
              s(8, 2688, 1856), s(128))
    compiled = jax.jit(step).lower(*shapes).compile()
    text = compiled.as_text()
    assert set(re.findall(r"%(moe_\w+?)(?:\.\d+)? = ", text)) == {
        "moe_experts_hidden", "moe_experts_down", "moe_experts_bwd",
        "moe_experts_wgrad"}
    assert _pallas_grids(jax.make_jaxpr(step)(*shapes).jaxpr) == {
        "moe_experts_hidden": (200,), "moe_experts_down": (200,),
        "moe_experts_bwd": (200,), "moe_experts_wgrad": (3, 200),
        "slot_combine": (69, 8)}
    _fits(compiled)


def test_state_space_scan_compiles_for_v5e(one_chip):
    """The seventh cell's Mamba-2 scan: 64 heads of 64 in 8 groups of a
    state of 128 over 8,192 rows, chunks of 128, float32 in, forward and
    backward: a grid step a (row of the batch, group, chunk), each head's
    64 x 128 state resident in VMEM from chunk to chunk, the chunk axis
    walked in order and in reverse."""
    from mxnet_tpu.ops.pallas_ops import ssd_scan

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    shapes = (s(1, 8192, 64, 64), s(1, 8192, 64), s(64), s(1, 8192, 8, 128),
              s(1, 8192, 8, 128), s(64))
    step = jax.value_and_grad(lambda *a: jnp.sum(ssd_scan(
        *a, interpret=False)), tuple(range(6)))
    compiled = jax.jit(step).lower(*shapes).compile()
    assert set(re.findall(r"%(ssd_scan_\w+?)(?:\.\d+)? = ",
                          compiled.as_text())) == {
        "ssd_scan_fwd", "ssd_scan_bwd"}
    assert _pallas_grids(jax.make_jaxpr(step)(*shapes).jaxpr) == {
        "ssd_scan_fwd": (1, 8, 64), "ssd_scan_bwd": (1, 8, 64)}
    _fits(compiled)
    # a group's heads share their products inside a grid step, in VMEM:
    # the step keeps no more in HBM than when each head had its own
    assert compiled.memory_analysis().temp_size_in_bytes <= SCAN_TEMP_BYTES


def test_mamba2_mixer_compiles_for_v5e(one_chip, monkeypatch):
    """The seventh cell's Mamba-2 mixer, a block of 2,688 over one sequence
    of 8,192 rows, float32, forward and backward, recomputed as its layers
    are: the convolution runs ``ssm_conv_fwd`` (twice: the recomputed pass)
    and ``ssm_conv_bwd``, a grid step a tile of 256 rows and a block of
    2,048 of the 6,144 channels, each block read out of the input
    projection's whole output (no copy of the slice); the scan its own two
    kernels."""
    from mxnet_tpu.gluon.block import functional_call
    from mxnet_tpu.gluon.nn.decoder_layers import Mamba2Mixer
    from mxnet_tpu.ops.pallas_ops import ATTENTION_RESIDUALS
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    net = Mamba2Mixer(2688, 64, 64, 128, 8, taps=4, chunk=128,
                      prefix="ssm_")
    shapes = {p.name: jax.ShapeDtypeStruct(p.shape, jnp.float32,
                                           sharding=one_chip)
              for p in net.collect_params().values()}
    x = jax.ShapeDtypeStruct((1, 8192, 2688), jnp.float32, sharding=one_chip)
    policy = jax.checkpoint_policies.save_only_these_names(
        *ATTENTION_RESIDUALS)

    def loss(values, x):
        out = jax.checkpoint(lambda v, x: functional_call(
            net, v, x, training=True)[0][0], policy=policy)(values, x)
        return jnp.sum(out * out)

    step = jax.value_and_grad(loss, (0, 1))
    compiled = jax.jit(step).lower(shapes, x).compile()
    text = compiled.as_text()
    calls = re.findall(r"%(\w+?)(?:\.\d+)? = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", text)
    assert sorted(calls) == ["ssd_scan_bwd", "ssd_scan_fwd", "ssd_scan_fwd",
                             "ssm_conv_bwd", "ssm_conv_fwd", "ssm_conv_fwd"]
    assert len(re.findall(r"%ssm_conv_fwd(?:\.\d+)? = [^\n]*"
                          r"operand_layout_constraints=\{f32\[1,8192,10304\]",
                          text)) == 2
    grids = _pallas_grids(jax.make_jaxpr(step)(shapes, x).jaxpr)
    assert grids["ssm_conv_fwd"] == grids["ssm_conv_bwd"] == (1, 32, 3)
    _fits(compiled)


def test_short_conv_decoder_s_blocks_compile_for_v5e(one_chip):
    """The causal kernels at head size 64 with 4 query heads a key/value
    head (32 to 8 over 2 sequences of 8,192 rows, float32 in, tiles of
    512 x 512: one key/value head's dk and dv are rows of 64, which take
    whole rows of 128 lanes in VMEM), forward and backward, a grid step for
    each of the 136 causal tiles; and the gated short convolution's two
    kernels over the same rows: tiles of 256 rows and all 2,048 channels of
    the three streams, 8-row blocks of the neighbouring tiles beside them."""
    from mxnet_tpu.ops.pallas_ops import causal_attention
    q = jax.ShapeDtypeStruct((2, 32, 8192, 64), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8, 8192, 64), jnp.float32,
                              sharding=one_chip)
    step = jax.grad(lambda q, k, v: jnp.sum(causal_attention(
        q, k, v, interpret=False)), (0, 1, 2))
    compiled = jax.jit(step).lower(q, kv, kv).compile()
    assert set(re.findall(r"%(attention_\w+?)(?:\.\d+)? = ",
                          compiled.as_text())) == {
        "attention_fwd", "attention_bwd"}
    assert _pallas_grids(jax.make_jaxpr(step)(q, kv, kv).jaxpr) == {
        "attention_fwd": (64, 136), "attention_bwd": (64, 136)}
    _fits(compiled)

    from mxnet_tpu.ops.pallas_ops import gated_short_conv
    streams = jax.ShapeDtypeStruct((2, 8192, 3 * 2048), jnp.float32,
                                   sharding=one_chip)
    taps = jax.ShapeDtypeStruct((2048, 3), jnp.float32, sharding=one_chip)
    step = jax.value_and_grad(lambda s, w: jnp.sum(gated_short_conv(
        s, w, interpret=False)), (0, 1))
    compiled = jax.jit(step).lower(streams, taps).compile()
    assert set(re.findall(r"%(short_conv_\w+?)(?:\.\d+)? = ",
                          compiled.as_text())) == {
        "short_conv_fwd", "short_conv_bwd"}
    assert _pallas_grids(jax.make_jaxpr(step)(streams, taps).jaxpr) == {
        "short_conv_fwd": (2, 32), "short_conv_bwd": (2, 32)}
    _fits(compiled)



@pytest.mark.parametrize("mask,steps", [("window", 93), ("causal", 528)])
def test_sixth_cell_s_attention_compiles_for_v5e(one_chip, mask, steps):
    """The sixth cell's two attention layers: 32 query heads to 4 of 128
    over one sequence of 16,384 rows, float32 in, tiles of 512 x 512,
    forward and backward.  The sliding layers' window of 1,024 keys visits
    the band of 93 tiles a head (the causal square's 528), each a grid step;
    the backward kernel holds one key/value head's whole dk and dv, 8 MiB
    each at 16,384 rows and twice for the pipeline, whatever the mask."""
    from mxnet_tpu.ops.pallas_ops import causal_attention, window_attention
    q = jax.ShapeDtypeStruct((1, 32, 16384, 128), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.float32,
                              sharding=one_chip)
    if mask == "window":
        attend = lambda q, k, v: window_attention(q, k, v, 1024,  # noqa: E731
                                                  interpret=False)
    else:
        attend = lambda q, k, v: causal_attention(q, k, v,  # noqa: E731
                                                  interpret=False)
    step = jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v)), (0, 1, 2))
    compiled = jax.jit(step).lower(q, kv, kv).compile()
    assert set(re.findall(r"%(attention_\w+?)(?:\.\d+)? = ",
                          compiled.as_text())) == {
        "attention_fwd", "attention_bwd"}
    assert _pallas_grids(jax.make_jaxpr(step)(q, kv, kv).jaxpr) == {
        "attention_fwd": (32, steps), "attention_bwd": (32, steps)}
    _fits(compiled)

# -- the fifth cell's dense feed-forward ---------------------------------------

_HLO_COMPUTATION = re.compile(r"(?:ENTRY )?%([\w.\-]+) .*\{\Z")
_HLO_INSTRUCTION = re.compile(
    r"\s+(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][a-z\-]*)\((.*)\Z")
_HLO_ARRAY = re.compile(r"\b(f32|bf16)\[([\d,]*)\]")


def _hlo_computations(text):
    """{computation: {instruction: (shape, opcode, operands, the rest)}}
    of an optimized module's text."""
    out, body = {}, None
    for line in text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            body = out.setdefault(head.group(1), {})
            continue
        got = _HLO_INSTRUCTION.match(line)
        if got and body is not None:
            name, shape, opcode, rest = got.groups()
            body[name] = (shape, opcode, re.findall(
                r"%([\w.\-]+)", rest.split(")", 1)[0]), rest)
    return out


def _array_bytes(shape):
    return sum(np.prod([int(n) for n in dims.split(",") if n], dtype=np.int64)
               * (4 if dtype == "f32" else 2)
               for dtype, dims in _HLO_ARRAY.findall(shape))


def _opcodes(computations, name):
    """The opcodes of a computation and of those it calls, at any depth."""
    out = set()
    for _, opcode, _, rest in computations[name].values():
        out.add(opcode)
        for called in re.findall(r"calls=%([\w.\-]+)", rest):
            out |= _opcodes(computations, called)
    return out


def _contractions_fed_by_exponentials(computations, name):
    """The contractions of a fused computation (at any depth) that read,
    through any chain of its instructions, an ``exponential`` or
    ``logistic``: an operand rebuilt inside the product."""
    body, exp = computations[name], {"exponential", "logistic"}

    def computes_exp(instr, seen):
        if instr in seen or instr not in body:
            return False
        seen.add(instr)
        _, opcode, operands, rest = body[instr]
        called = re.search(r"calls=%([\w.\-]+)", rest)
        return opcode in exp or bool(
            called and _opcodes(computations, called.group(1)) & exp) \
            or any(computes_exp(o, seen) for o in operands)

    found = [i for i, (_, opcode, operands, _) in body.items()
             if opcode in ("convolution", "dot")
             and any(computes_exp(o, set()) for o in operands)]
    for _, opcode, _, rest in body.values():
        called = re.search(r"calls=%([\w.\-]+)", rest)
        if opcode == "fusion" and called:
            found += _contractions_fed_by_exponentials(computations,
                                                       called.group(1))
    return found


def test_dense_feed_forward_s_backward_reads_its_operands_once(one_chip,
                                                               monkeypatch):
    """The fifth cell's dense layer (16,384 rows of 2,048, width 11,776,
    float32, recomputed as ``hybridize(remat=True)`` does it) trained with
    Adam's update in the same program: the backward pass writes ``dg``,
    ``du`` and ``h`` once in bfloat16, so no contraction of its five reads
    an operand rebuilt from float32 ``g``, ``u`` and ``dh`` with an
    exponential, and each weight gradient, Adam's update fused in, reads
    bfloat16 rows: 0.74 GB where the products of XLA's derivative read 1.90
    to 2.74."""
    from mxnet_tpu.ops.decoder_ops import gated_mlp
    # the operator asks the backend which dtype the matrix units take
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, d, f = 16384, 2048, 11776

    def step(x, dy, weights, means, variances):
        def loss(x, weights):
            return jnp.sum(jax.checkpoint(gated_mlp)(x, *weights) * dy)
        dx, grads = jax.grad(loss, (0, 1))(x, weights)
        new = [(w - 1e-4 * m / (jnp.sqrt(v) + 1e-8), m, v)
               for w, m, v in ((w, 0.9 * m + 0.1 * g, 0.95 * v + 0.05 * g * g)
                               for w, g, m, v in zip(weights, grads, means,
                                                     variances))]
        return dx, new

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    w = (s(f, d), s(f, d), s(d, f))
    compiled = jax.jit(step).lower(s(2, rows // 2, d), s(2, rows // 2, d),
                                   w, w, w).compile()
    computations = _hlo_computations(compiled.as_text())
    entry = [c for c in computations if c.startswith("main")][-1]
    wgrads = []
    for name, (shape, opcode, operands, rest) in computations[entry].items():
        called = re.search(r"calls=%([\w.\-]+)", rest)
        if opcode != "fusion" or "mlp.dense" not in rest or not _opcodes(
                computations, called.group(1)) & {"convolution", "dot"}:
            continue
        assert not _contractions_fed_by_exponentials(
            computations, called.group(1)), name
        if _HLO_ARRAY.search(shape).group(2) in ("%d,%d" % (f, d),
                                                 "%d,%d" % (d, f)):
            wgrads.append([computations[entry][o][0] for o in operands])
    assert len(wgrads) == 3
    for shapes in wgrads:
        read = [s for s in shapes if re.match(r"\w+\[%d," % rows, s)]
        assert len(read) == 2 and all(s.startswith("bf16[") for s in read)
        assert sum(_array_bytes(s) for s in shapes) < 0.9e9
    _fits(compiled)
