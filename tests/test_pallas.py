"""Pallas kernels (interpret mode on CPU; same kernels compile for TPU)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import profiler
from mxnet_tpu.ops import pallas_ops
from mxnet_tpu.ops.pallas_ops import (flash_attention, _flash_attention_pallas,
                                      _attention_reference)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    rng = np.random.RandomState(0)
    B, H, T, D = 2, 2, 256, 64
    q = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype(np.float32))
    out_p = _flash_attention_pallas(q, k, v, causal, 1.0 / np.sqrt(D),
                                    interpret=True)
    out_r = _attention_reference(q, k, v, causal, 1.0 / np.sqrt(D))
    assert float(jnp.max(jnp.abs(out_p - out_r))) < 2e-5


def test_flash_attention_grad():
    rng = np.random.RandomState(1)
    B, H, T, D = 1, 1, 128, 32
    q = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype(np.float32))

    def loss_flash(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(_attention_reference(q_, k_, v_, True,
                                            1.0 / np.sqrt(D)) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-3


def test_flash_attention_op_registered():
    from mxnet_tpu.ndarray import invoke
    from mxnet_tpu import nd
    rng = np.random.RandomState(2)
    x = nd.array(rng.normal(0, 1, (1, 2, 128, 16)).astype(np.float32))
    out = invoke("_contrib_flash_attention", [x, x, x], {"causal": True})
    assert out.shape == (1, 2, 128, 16)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("T,Tk", [(200, 200), (130, 130), (100, 100),
                                  (160, 224)])
def test_flash_attention_ragged_lengths(causal, T, Tk):
    """T % 128 != 0 stays on the fused kernel: the tail q/k blocks are
    padded to the tile size and masked, not routed to the dense fallback."""
    if causal and T != Tk:
        causal = "bottom"  # bare True is ambiguous for mismatched lengths
    rng = np.random.RandomState(3)
    B, H, D = 1, 2, 32
    q = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (B, H, Tk, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (B, H, Tk, D)).astype(np.float32))
    out_p = _flash_attention_pallas(q, k, v, causal, 1.0 / np.sqrt(D),
                                    interpret=True)
    out_r = _attention_reference(q, k, v, causal, 1.0 / np.sqrt(D))
    assert out_p.shape == (B, H, T, D)
    assert float(jnp.max(jnp.abs(out_p - out_r))) < 2e-5


def test_flash_attention_causal_ragged_qk_rejected():
    """Bare causal=True with T != Tk has ambiguous position alignment; the
    entry refuses loudly and names the two explicit conventions."""
    q = jnp.zeros((1, 1, 130, 16), jnp.float32)
    k = jnp.zeros((1, 1, 200, 16), jnp.float32)
    with pytest.raises(ValueError, match="ambiguous"):
        flash_attention(q, k, k, causal=True, interpret=True)


@pytest.mark.parametrize("align", ["top", "bottom"])
def test_flash_attention_causal_alignment(align):
    """Explicit 'top'/'bottom' alignment resolves the ragged-causal case:
    'bottom' is the KV-cache decode convention (last query sees every key),
    'top' aligns query 0 with key 0."""
    rng = np.random.RandomState(4)
    B, H, T, Tk, D = 1, 2, 96, 224, 32
    q = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (B, H, Tk, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (B, H, Tk, D)).astype(np.float32))
    out_p = _flash_attention_pallas(q, k, v, align, 1.0 / np.sqrt(D),
                                    interpret=True)
    out_r = _attention_reference(q, k, v, align, 1.0 / np.sqrt(D))
    assert float(jnp.max(jnp.abs(out_p - out_r))) < 2e-5
    # reference semantics spot-check against an explicit dense mask
    off = Tk - T if align == "bottom" else 0
    mask = (np.arange(Tk)[None, :] <= np.arange(T)[:, None] + off)
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q),
                  np.asarray(k)) / np.sqrt(D)
    s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    dense = np.einsum("bhqk,bhkd->bhqd", p, np.asarray(v))
    np.testing.assert_allclose(np.asarray(out_r), dense, atol=2e-5)


def test_flash_attention_kv_cache_decode():
    """T=1 decode against a long KV cache: causal='bottom' attends every
    key (== non-causal for a single query) and works through the entry."""
    rng = np.random.RandomState(5)
    B, H, Tk, D = 1, 2, 200, 32
    q = jnp.asarray(rng.normal(0, 1, (B, H, 1, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (B, H, Tk, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (B, H, Tk, D)).astype(np.float32))
    out = flash_attention(q, k, v, causal="bottom", interpret=True)
    full = flash_attention(q, k, v, causal=False, interpret=True)
    assert out.shape == (B, H, 1, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full), atol=2e-5)


# -- the list of visited tiles that every kernel's grid walks -----------------

# mask, query rows, key rows, (block_q, block_k)
TILE_LISTS = {
    "none_ragged": (("none",), 200, 130, (64, 32)),
    "causal_top_fewer_queries": (
        pallas_ops._causal_mask("top", 100, 200), 100, 200, (32, 64)),
    "causal_top_fewer_keys": (
        pallas_ops._causal_mask("top", 200, 100), 200, 100, (64, 32)),
    "causal_bottom_fewer_queries": (
        pallas_ops._causal_mask("bottom", 70, 200), 70, 200, (32, 64)),
    "block_diffusion": (
        pallas_ops.block_diffusion_mask(96, 4), 192, 192, (64, 32)),
    "block_diffusion_ragged": (
        pallas_ops.block_diffusion_mask(100, 4), 200, 200, (32, 64)),
    "data_ragged": (pallas_ops.DATA_MASK, 100, 100, (32, 64)),
    "a_query_tile_without_keys": (("causal", -40), 128, 128, (32, 32)),
}


@pytest.mark.parametrize("case", sorted(TILE_LISTS))
def test_tile_list_holds_each_visible_tile_once_query_tile_major(case):
    """One entry for every tile with a visible pair of real rows, query tile
    by query tile and ascending in the key tile; every query tile has an
    entry (one of state 0 if it sees nothing) and its first and last are
    marked; the recorder counts a grid step an entry."""
    mask, Tq, Tk, tiles = TILE_LISTS[case]
    heads = 2
    plan = pallas_ops._Plan((1, heads, Tq, 16), (1, 1, Tk, 16), mask, 0.25,
                            tiles[0], tiles[1], jnp.float32, True)
    bq, bk, n_q, n_k = plan.block_q, plan.block_k, plan.n_q, plan.n_k
    data = mask == pallas_ops.DATA_MASK     # the causal mask's, all masked
    rows, cols = np.arange(n_q * bq)[:, None], np.arange(n_k * bk)[None, :]
    seen = pallas_ops.mask_visible(("causal", 0) if data else mask, rows,
                                   cols) & (cols < Tk)
    some = (seen & (rows < Tq)).reshape(n_q, bq, n_k, bk).any(axis=(1, 3))
    every = seen.reshape(n_q, bq, n_k, bk).all(axis=(1, 3)) & (not data)
    want = []
    for qi in range(n_q):
        want += [(qi, int(ki), 2 if every[qi, ki] else 1)
                 for ki in np.nonzero(some[qi])[0]] or [(qi, 0, 0)]
    state = plan.flag & pallas_ops._STATE
    assert list(zip(plan.q_tile.tolist(), plan.k_tile.tolist(),
                    state.tolist())) == want
    assert plan.steps == len(want)
    turns = (np.diff(plan.q_tile) != 0).tolist()
    assert ((plan.flag & pallas_ops._FIRST) != 0).tolist() == [True] + turns
    assert ((plan.flag & pallas_ops._LAST) != 0).tolist() == turns + [True]

    profiler.reset_spans()
    plan.count_tiles()
    totals = profiler.totals()
    assert totals["attn.tiles_total"]["count"] == heads * n_q * n_k
    assert totals["attn.tiles_visited"]["count"] == heads * some.sum()
    assert totals["attn.grid_steps"]["count"] == heads * len(want) \
        == heads * (some.sum() + (~some.any(axis=1)).sum())


# mask, rows, (block_q, block_k): the rows' lists differ most in length
UNEVEN_ROWS = {
    "causal_8_query_tiles": (("causal", 0), 256, (32, 32)),
    "block_diffusion_64x32": (
        pallas_ops.block_diffusion_mask(64, 4), 128, (64, 32)),
    "block_diffusion_32x64": (
        pallas_ops.block_diffusion_mask(64, 4), 128, (32, 64)),
    "a_query_tile_without_keys": (("causal", -40), 128, (32, 32)),
}


@pytest.mark.parametrize("case", sorted(UNEVEN_ROWS))
def test_kernels_over_uneven_rows_match_reference_8_heads_to_1_batch_2(case):
    """Forward and gradients over a list whose query tiles hold 1 to 8
    entries.  A row that sees no key is written as zeros by the kernel and
    as a uniform softmax by the reference: its cotangent is 0 here."""
    mask, T, tiles = UNEVEN_ROWS[case]
    rng = np.random.RandomState(7)
    dim, scale = 16, 0.25
    q = jnp.asarray(rng.normal(0, 1, (2, 8, T, dim)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (2, 1, T, dim)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (2, 1, T, dim)), jnp.float32)
    sees = pallas_ops.mask_visible(mask, np.arange(T)[:, None],
                                   np.arange(T)[None, :]).any(axis=1)
    weight = jnp.asarray(rng.normal(0, 1, q.shape) * sees[:, None],
                         jnp.float32)

    def kernels(q, k, v):
        out = pallas_ops._attention(q, k, v, mask, scale, "highest", True,
                                    *tiles)
        return jnp.sum(weight * out), out

    def oracle(q, k, v):
        out = _attention_reference(q, k, v, None, scale, mask=mask)
        return jnp.sum(weight * out), out

    (_, out), grads = jax.value_and_grad(kernels, (0, 1, 2), has_aux=True)(
        q, k, v)
    (_, want), want_grads = jax.value_and_grad(oracle, (0, 1, 2),
                                               has_aux=True)(q, k, v)
    assert not np.asarray(out)[:, :, ~sees].any()
    assert float(jnp.max(jnp.abs(out - want)[:, :, sees])) < 2e-5
    for got, ref in zip(grads, want_grads):
        assert got.shape == ref.shape
        assert float(jnp.max(jnp.abs(got - ref))) < 1e-4


def test_rtc_pallas_module_user_kernel():
    """mx.rtc.PallasModule is the runtime-kernel extension point (the
    CudaModule analog): a user-written pallas kernel launches on NDArrays."""
    from jax.experimental import pallas as pl
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    def scaled_add_kernel(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0 + y_ref[...]

    def scaled_add(x, y):
        return pl.pallas_call(
            scaled_add_kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True,  # CPU CI; compiles natively on TPU
        )(x, y)

    mod = mx.rtc.PallasModule({"scaled_add": scaled_add})
    kern = mod.get_kernel("scaled_add")
    a = nd.array(np.arange(8.0, dtype=np.float32))
    b = nd.ones((8,))
    out = kern.launch([a, b])
    np.testing.assert_allclose(out.asnumpy(), np.arange(8.0) * 2 + 1)

    with pytest.raises(NotImplementedError):
        mx.rtc.CudaModule("__global__ void k() {}")
