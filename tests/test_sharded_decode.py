"""Sharded decode: tensor-parallel serving over the ('tp','sp') mesh
(docs/SERVING.md "Sharded decode").

Tier-1 gates for the sharded-decode tentpole:

* **Compute-parallel tensor parallelism** — a ``ShardedDecodeModel(tp=2)``
  engine (head-sharded K/V pools, Megatron column/row-parallel matmuls,
  zero gathers on the decode step) serves greedy AND seeded-sampled
  streams token-identical to the single-device reference, with zero
  steady-state recompiles and zero leaked blocks; prefix caching, CoW,
  chunked prefill and speculative verify compose unchanged.  Logits are
  allclose (not bitwise) to the reference: the per-block psum reduces
  partial products in a different order than the unsharded matmul.
* **Eager shape validation** — heads/tp divisibility, pool layout vs the
  mesh, device budget, and parameter PartitionSpecs all fail as
  ValueErrors naming BOTH extents (the ``shard_batch`` convention), never
  as shape errors inside ``shard_map``.
* **Handoffs across geometries** — sharded→sharded AND sharded↔unsharded
  stream migrations stay bitwise (exported pages host-gather to the full
  head axis; the importer re-shards), sampler state included.
* **Gluon adapter** — ``GluonCausalLMAdapter`` serves a role-named
  HybridBlock (native, exported/re-imported, and wrapped in
  ``ShardedDecodeModel(tp=2)``) bitwise-equal to the native contract
  model; role discovery errors name the candidates.
* **Fused long-context / MoE paths** — ``long_context_attention`` routes
  Ulysses/ring inside shard_map (allclose to dense, fallback on short
  buckets) and ``expert_sharded_ffn`` matches its single-member run.
* **Fleet accounting** — a tp=k engine consumes k devices in
  ``scaling_advice``, KV headroom never double-counts shards, a
  tp-mismatched factory fails the load loudly, and ``<engine>:tp_degree``
  lands in the profiler dump.
* **Chaos + bench** — the mxstress ``sharded_decode`` scenario holds over
  FAULT_SMOKE_SEEDS, and ``serve_bench --profile sharded-decode`` (smoke)
  meets its count gates: a gather-free decode step (2L+2 psums,
  statically predicted) and static == runtime peak bytes.
* **Quantized wire** — opt-in ``wire="2bit"`` swaps the per-block psums
  for the PR 10 2-bit codec (assembly + unembed psums stay exact fp32):
  codec round-trip is bitwise at representable inputs, end-to-end logits
  stay finite inside a documented loose envelope, and the counter bill
  drops from 4-byte to 1-byte wire words on the block psums.
"""
import json
import os
import sys
import time

import numpy as np
import pytest

from mxnet_tpu.serving import OK
from mxnet_tpu.serving.decode import (DecodeEngine, GluonCausalLMAdapter,
                                      ShardedDecodeModel, TinyCausalLM,
                                      TinyGluonLM, decode_mesh,
                                      expert_sharded_ffn,
                                      long_context_attention)
from mxnet_tpu.serving.decode.adapter import (copy_reference_weights,
                                              discover_roles)
from mxnet_tpu.serving.decode.sharding import (check_pool_matches_mesh,
                                               check_tp_divisible)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROMPT = [5, 3, 7, 1, 2, 6, 4, 8]           # two full prefill chunks
_PROMPTS = [list(_PROMPT), [5, 3, 7, 1], [2, 6, 4], [9, 8, 1, 2, 3]]
_MODEL_KW = dict(vocab_size=32, hidden=16, num_layers=1, num_heads=2,
                 max_len=48, seed=3)
_SAMPLE = dict(temperature=0.8, top_k=6, seed=123)


@pytest.fixture(scope="module")
def model():
    return TinyCausalLM(**_MODEL_KW)


@pytest.fixture(scope="module")
def sh_model():
    # same seed as `model`: identical params is what makes the bitwise
    # sharded-vs-single-device comparison meaningful
    return ShardedDecodeModel(TinyCausalLM(**_MODEL_KW), tp=2)


def _engine(m, name, **over):
    kw = dict(max_slots=4, block_size=4, num_blocks=24, max_prompt_len=8,
              max_new_tokens=10, prefill_chunk=4, prefix_cache=True)
    kw.update(over)
    return DecodeEngine(m, name=name, **kw)


@pytest.fixture(scope="module")
def ref_eng(model):
    eng = _engine(model, "shref")
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def sh_eng(sh_model):
    eng = _engine(sh_model, "shtp2")
    yield eng
    eng.stop()


def _leak(engine):
    kv = engine.kv_stats()
    return kv["allocated_total"] - kv["freed_total"]


# ---------------------------------------------------------------------------
# eager validation: ValueErrors name both extents, never shard_map shapes
# ---------------------------------------------------------------------------

def test_check_tp_divisible_names_both_extents():
    with pytest.raises(ValueError, match=r"m: head count of 3 is not "
                                         r"divisible by the mesh 'tp' axis "
                                         r"extent 2"):
        check_tp_divisible("m", 3, 2)
    assert check_tp_divisible("m", 4, 2) == 2


def test_pool_shape_validation_names_layout_and_extents():
    mesh = decode_mesh(2)
    with pytest.raises(ValueError, match="contract layout"):
        check_pool_matches_mesh("m", (2, 3, 4), mesh)
    with pytest.raises(ValueError, match=r"pool head axis of 3 is not "
                                         r"divisible"):
        check_pool_matches_mesh("m", (1, 8, 4, 3, 4), mesh)
    assert check_pool_matches_mesh("m", (1, 8, 4, 4, 4), mesh) == 2


def test_decode_mesh_exact_size_and_device_budget():
    mesh = decode_mesh(2, 2)
    assert dict(mesh.shape) == {"tp": 2, "sp": 2}
    assert mesh.devices.size == 4            # exactly tp*sp, never folded
    with pytest.raises(ValueError,
                       match=r"tp=5 x sp=2 needs 10 device\(s\); only 8"):
        decode_mesh(5, 2)
    with pytest.raises(ValueError, match="must both be >= 1"):
        decode_mesh(0)


def test_sharded_model_rejects_indivisible_heads():
    odd = TinyCausalLM(vocab_size=20, hidden=12, num_layers=1, num_heads=3,
                       max_len=16, seed=1)
    with pytest.raises(ValueError, match=r"head count of 3 is not "
                                         r"divisible by the mesh 'tp' axis "
                                         r"extent 2"):
        ShardedDecodeModel(odd, tp=2)


class _SpecOverride:
    """Wrap a contract model but dictate its partition_specs()."""

    def __init__(self, inner, specs):
        self._inner = inner
        self._specs = specs

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def partition_specs(self):
        return self._specs


def test_partition_spec_validation_is_eager():
    from jax.sharding import PartitionSpec as P
    inner = TinyCausalLM(vocab_size=33, hidden=16, num_layers=1,
                         num_heads=2, max_len=16, seed=1)
    with pytest.raises(ValueError,
                       match="supports only the 'tp' mesh axis"):
        ShardedDecodeModel(_SpecOverride(inner, {"embed": P("dp", None)}),
                           tp=2)
    with pytest.raises(ValueError,
                       match="dim 0 extent of 33 is not divisible"):
        ShardedDecodeModel(_SpecOverride(inner, {"embed": P("tp", None)}),
                           tp=2)
    with pytest.raises(ValueError, match="3 entries for a rank-2"):
        ShardedDecodeModel(
            _SpecOverride(inner, {"embed": P(None, None, "tp")}), tp=2)


def test_zeros_pool_validates_contract_shape(sh_model):
    with pytest.raises(ValueError, match="contract layout"):
        sh_model.zeros_pool((4, 4, 4))
    with pytest.raises(ValueError, match="pool head axis of 3"):
        sh_model.zeros_pool((1, 8, 4, 3, 8))
    pool = sh_model.zeros_pool((1, 8, 4, 2, 8))
    assert tuple(pool.shape) == (1, 8, 4, 2, 8)


# ---------------------------------------------------------------------------
# bitwise tensor-parallel serving
# ---------------------------------------------------------------------------

def test_sharded_streams_bitwise_greedy_and_sampled(ref_eng, sh_eng):
    for p in _PROMPTS:
        ref = ref_eng.generate_reference(p, 8).tolist()
        s = sh_eng.submit(list(p), 8, timeout_ms=30000)
        assert s.result().status == OK
        assert list(s.tokens()) == ref
    for p in _PROMPTS:
        ref = ref_eng.generate_reference(p, 8, **_SAMPLE).tolist()
        s = sh_eng.submit(list(p), 8, timeout_ms=30000, **_SAMPLE)
        assert s.result().status == OK
        assert list(s.tokens()) == ref
    assert _leak(sh_eng) == 0


def test_sharded_steady_state_zero_recompiles(sh_eng):
    # warm both stream kinds first, then require the full mixed workload
    # to ride the existing signatures
    for kw in ({}, dict(_SAMPLE)):
        assert sh_eng.submit(list(_PROMPT), 8, timeout_ms=30000,
                             **kw).result().status == OK
    before = sh_eng.stats_snapshot()["cache"]["recompiles"]
    for p in _PROMPTS:
        for kw in ({}, dict(_SAMPLE)):
            assert sh_eng.submit(list(p), 8, timeout_ms=30000,
                                 **kw).result().status == OK
    assert sh_eng.stats_snapshot()["cache"]["recompiles"] == before
    assert _leak(sh_eng) == 0


def test_sharded_composes_with_prefix_cow_chunk_spec(sh_model, ref_eng):
    eng = _engine(sh_model, "shspec", spec_k=2, draft_model=sh_model)
    try:
        ref = ref_eng.generate_reference(_PROMPT, 8).tolist()
        donor = eng.submit(list(_PROMPT), 8)
        assert donor.result().status == OK
        assert list(donor.tokens()) == ref
        dup = eng.submit(list(_PROMPT), 8)      # full hit + CoW tail fork
        assert dup.result().status == OK
        assert list(dup.tokens()) == ref
        sam_ref = ref_eng.generate_reference(_PROMPT, 8, **_SAMPLE).tolist()
        sam = eng.submit(list(_PROMPT), 8, **_SAMPLE)
        assert sam.result().status == OK
        assert list(sam.tokens()) == sam_ref
        snap = eng.stats_snapshot()
        assert snap["prefix_hits"] >= 1
        assert snap["spec_proposed"] >= 1 and snap["spec_accepted"] >= 1
        assert _leak(eng) == 0
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# handoff: sharded→sharded and sharded↔unsharded stay bitwise
# ---------------------------------------------------------------------------

def _poll_partial(streams, min_tokens=3, deadline_s=10.0):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        pending = False
        for s in streams:
            status, tokens, _, _, _ = s.snapshot()
            if status is None and len(tokens) < min_tokens:
                pending = True
        if not pending:
            return
        time.sleep(0.005)


def _migrate(src, dst):
    assert src.quiesce()
    moved = src.export_streams()
    src.resume()
    for stream, snap in moved:
        stream.set_owner("mig")
        dst.import_stream(snap, stream=stream, owner="mig")


def test_handoff_sharded_to_sharded_bitwise(sh_model, ref_eng):
    a = _engine(sh_model, "sh2a", max_slots=2, max_new_tokens=10)
    b = _engine(sh_model, "sh2b", max_slots=2, max_new_tokens=10)
    try:
        ref = ref_eng.generate_reference(_PROMPT, 10).tolist()
        ref_sam = ref_eng.generate_reference(_PROMPT, 10,
                                             temperature=0.8,
                                             seed=555).tolist()
        greedy = a.submit(list(_PROMPT), 10)
        sampled = a.submit(list(_PROMPT), 10, temperature=0.8, seed=555)
        _poll_partial([greedy, sampled])
        _migrate(a, b)
        assert greedy.result().status == OK
        assert sampled.result().status == OK
        assert list(greedy.tokens()) == ref
        # the importer continues the EXACT uniform draw sequence
        assert list(sampled.tokens()) == ref_sam
        assert _leak(a) == 0
    finally:
        a.stop()
        b.stop()
    assert _leak(b) == 0


def test_handoff_across_geometries_bitwise(sh_model, model, ref_eng):
    # one engine pair covers both directions: sharded→unsharded first,
    # then fresh streams back unsharded→sharded
    a = _engine(sh_model, "shxa", max_slots=2, max_new_tokens=10)
    b = _engine(model, "shxb", max_slots=2, max_new_tokens=10)
    try:
        ref = ref_eng.generate_reference(_PROMPT, 10).tolist()
        ref_sam = ref_eng.generate_reference(_PROMPT, 10,
                                             temperature=0.8,
                                             seed=777).tolist()
        down = a.submit(list(_PROMPT), 10)
        down_sam = a.submit(list(_PROMPT), 10, temperature=0.8, seed=777)
        _poll_partial([down, down_sam])
        _migrate(a, b)                  # exported pages carry FULL heads
        assert down.result().status == OK
        assert down_sam.result().status == OK
        assert list(down.tokens()) == ref
        assert list(down_sam.tokens()) == ref_sam

        up = b.submit(list(_PROMPT), 10)
        up_sam = b.submit(list(_PROMPT), 10, temperature=0.8, seed=777)
        _poll_partial([up, up_sam])
        _migrate(b, a)                  # the importer re-shards the pages
        assert up.result().status == OK
        assert up_sam.result().status == OK
        assert list(up.tokens()) == ref
        assert list(up_sam.tokens()) == ref_sam
        assert _leak(a) == 0 and _leak(b) == 0
    finally:
        a.stop()
        b.stop()


# ---------------------------------------------------------------------------
# Gluon-block adapter: native, exported, and sharded serving stay bitwise
# ---------------------------------------------------------------------------

_GLUON_KW = dict(vocab_size=_MODEL_KW["vocab_size"],
                 hidden=_MODEL_KW["hidden"],
                 num_layers=_MODEL_KW["num_layers"],
                 num_heads=_MODEL_KW["num_heads"],
                 max_len=_MODEL_KW["max_len"])


@pytest.fixture(scope="module")
def gluon_block(model):
    block = TinyGluonLM(prefix="lm_", **_GLUON_KW)
    block.collect_params().initialize()
    copy_reference_weights(block, model)
    return block


def _expected(ref_eng, sampled_idx):
    out = []
    for i, p in enumerate(_PROMPTS):
        kw = dict(_SAMPLE) if i in sampled_idx else {}
        out.append(ref_eng.generate_reference(p, 8, **kw).tolist())
    return out


def _serve(m, name, sampled_idx):
    eng = DecodeEngine(m, name=name, max_slots=4, block_size=4,
                       num_blocks=24, max_prompt_len=8)
    try:
        outs = []
        for i, p in enumerate(_PROMPTS):
            kw = dict(max_new_tokens=8, timeout_ms=30000)
            if i in sampled_idx:
                kw.update(_SAMPLE)
            s = eng.submit(list(p), **kw)
            assert s.result().status == OK
            outs.append(list(s.tokens()))
        assert _leak(eng) == 0
        return outs
    finally:
        eng.stop()


def test_adapter_serves_bitwise_vs_native(gluon_block, ref_eng):
    adapter = GluonCausalLMAdapter(gluon_block,
                                   num_heads=_GLUON_KW["num_heads"])
    assert adapter.vocab_size == _MODEL_KW["vocab_size"]
    assert adapter.num_layers == _MODEL_KW["num_layers"]
    assert _serve(adapter, "adnat", {1}) == _expected(ref_eng, {1})


def test_adapter_export_roundtrip_serves_bitwise(gluon_block, ref_eng,
                                                 tmp_path):
    import mxnet_tpu.ndarray as nd
    from mxnet_tpu.gluon.block import SymbolBlock
    prefix = str(tmp_path / "lm")
    gluon_block(nd.array(np.array([_PROMPT], dtype=np.int32)))
    gluon_block.export(prefix)
    imported = SymbolBlock.imports(prefix + "-symbol.json", ["data"],
                                   prefix + "-0000.params")
    adapter = GluonCausalLMAdapter(imported,
                                   num_heads=_GLUON_KW["num_heads"])
    assert _serve(adapter, "adexp", {2}) == _expected(ref_eng, {2})


def test_sharded_adapter_tp2_serves_bitwise(gluon_block, ref_eng):
    adapter = GluonCausalLMAdapter(gluon_block,
                                   num_heads=_GLUON_KW["num_heads"])
    sh = ShardedDecodeModel(adapter, tp=2)
    assert sh.tp_degree == 2
    assert _serve(sh, "adtp2", {3}) == _expected(ref_eng, {3})


def test_adapter_role_discovery_errors():
    with pytest.raises(ValueError, match="ambiguous"):
        discover_roles(["a_l0_wq_weight", "b_l0_wq_weight",
                        "embed_weight", "pos_weight"])
    with pytest.raises(ValueError,
                       match=r"no parameter matches role 'embed'"):
        discover_roles(["pos_weight", "l0_wq_weight"])
    with pytest.raises(ValueError,
                       match="not among the block's parameters"):
        discover_roles(["embed_weight", "pos_weight"],
                       layer_map={"l0_wq": "nope"})


def test_adapter_rejects_indivisible_heads(gluon_block):
    with pytest.raises(ValueError, match="hidden size 16 is not divisible "
                                         "by num_heads 3"):
        GluonCausalLMAdapter(gluon_block, num_heads=3)


# ---------------------------------------------------------------------------
# fused long-context / MoE paths inside shard_map
# ---------------------------------------------------------------------------

def _sp_mesh(n):
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:n]), ("sp",))


def _run_replicated(mesh, fn, *args):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    wrapped = shard_map(fn, mesh=mesh,
                        in_specs=tuple(P() for _ in args), out_specs=P(),
                        check_vma=False)
    return wrapped(*args)


def _dense_attention(q, k, v, causal):
    scores = np.einsum("bhtd,bhsd->bhts", q, k) / np.sqrt(q.shape[-1])
    if causal:
        T = q.shape[2]
        mask = np.tril(np.ones((T, T), dtype=bool))
        scores = np.where(mask[None, None], scores, -1e30)
    scores -= scores.max(axis=-1, keepdims=True)
    w = np.exp(scores)
    w /= w.sum(axis=-1, keepdims=True)
    return np.einsum("bhts,bhsd->bhtd", w, v)


def test_long_context_attention_routes_and_falls_back():
    rng = np.random.RandomState(0)
    mesh = _sp_mesh(2)
    # H=4 divides the axis -> Ulysses
    q, k, v = (rng.randn(2, 4, 8, 8).astype(np.float32) for _ in range(3))
    out = _run_replicated(
        mesh, lambda a, b, c: long_context_attention(a, b, c), q, k, v)
    np.testing.assert_allclose(np.asarray(out),
                               _dense_attention(q, k, v, True),
                               rtol=2e-4, atol=2e-5)
    # H=3 does not divide -> ring
    q3, k3, v3 = (rng.randn(1, 3, 8, 8).astype(np.float32)
                  for _ in range(3))
    out3 = _run_replicated(
        mesh, lambda a, b, c: long_context_attention(a, b, c), q3, k3, v3)
    np.testing.assert_allclose(np.asarray(out3),
                               _dense_attention(q3, k3, v3, True),
                               rtol=2e-4, atol=2e-5)
    # T % n != 0 routes to the model's own dense attention...
    q7, k7, v7 = (rng.randn(1, 2, 7, 8).astype(np.float32)
                  for _ in range(3))
    out7 = _run_replicated(
        mesh,
        lambda a, b, c: long_context_attention(a, b, c,
                                               fallback=lambda x, y, z: x),
        q7, k7, v7)
    np.testing.assert_allclose(np.asarray(out7), q7)
    # ...and without one, raises naming BOTH extents at trace time
    with pytest.raises(ValueError, match=r"sequence length of 7 is not "
                                         r"divisible by the mesh 'sp' axis "
                                         r"extent 2"):
        _run_replicated(
            mesh, lambda a, b, c: long_context_attention(a, b, c),
            q7, k7, v7)


def test_expert_sharded_ffn_matches_single_member():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    rng = np.random.RandomState(1)
    E, T, d = 4, 8, 6
    w = (rng.randn(E, d, d) * 0.1).astype(np.float32)
    gate = rng.randn(d, E).astype(np.float32)
    x = rng.randn(T, d).astype(np.float32)

    def expert_fn(we, toks):
        return toks @ we

    def run(n):
        mesh = _sp_mesh(n)
        f = shard_map(
            lambda wl, g, xx: expert_sharded_ffn(expert_fn, wl, g, xx),
            mesh=mesh, in_specs=(P("sp"), P(), P()), out_specs=P(),
            check_vma=False)
        return np.asarray(f(w, gate, x))

    np.testing.assert_allclose(run(2), run(1), rtol=2e-5, atol=2e-5)
    # validation names both extents, not a collective shape error
    mesh = _sp_mesh(2)
    with pytest.raises(ValueError, match="token count of 7 is not "
                                         "divisible"):
        shard_map(
            lambda wl, g, xx: expert_sharded_ffn(expert_fn, wl, g, xx),
            mesh=mesh, in_specs=(P("sp"), P(), P()), out_specs=P(),
            check_vma=False)(w, gate, x[:7])
    with pytest.raises(ValueError, match="expert count of 3 is not "
                                         "divisible"):
        shard_map(
            lambda wl, g, xx: expert_sharded_ffn(expert_fn, wl, g, xx),
            mesh=mesh, in_specs=(P("sp"), P(), P()), out_specs=P(),
            check_vma=False)(w, gate[:, :3], x)


# ---------------------------------------------------------------------------
# fleet accounting: device footprint, headroom, mismatch, profiler counter
# ---------------------------------------------------------------------------

_FLEET_CFG = dict(vocab_size=20, hidden=16, num_layers=1, num_heads=2,
                  max_len=24, seed=13)
_FLEET_EKW = dict(max_slots=2, block_size=4, num_blocks=9, max_prompt_len=4,
                  max_new_tokens=5, max_queue=6, width_blocks=[4])


def _fleet_factory(tp, sp=1):
    def make(name):
        m = TinyCausalLM(**_FLEET_CFG)
        if tp * sp > 1:
            m = ShardedDecodeModel(m, tp=tp, sp=sp)
        return DecodeEngine(m, name=name, **_FLEET_EKW)
    return make


def test_fleet_places_a_tp_by_sp_mesh_on_a_window_of_free_devices():
    """The window an engine is given is as wide as its mesh, tp*sp: with
    device 2 taken and 0-1 free, a 2x2 mesh goes to 3-6, not onto 0-3."""
    from mxnet_tpu.serving.fleet import FleetRouter
    r = FleetRouter(replicas=1)
    try:
        for name in "abc":      # one chip each: devices 0, 1, 2
            r.load_decode(name, _fleet_factory(1))
        r.unload_decode("a")
        r.unload_decode("b")
        r.load_decode("lm", _fleet_factory(2, sp=2), tp=2, sp=2)
        rid = r.stats()["decode_models"]["lm"]["placement"][0]
        held = {n: [d.id for d in r.engine(n, rid).devices]
                for n in ("c", "lm")}
        assert held == {"c": [2], "lm": [3, 4, 5, 6]}
        assert r.scaling_advice()["devices_in_use"] == 5
        s = r.submit_stream("lm", [1, 2, 3], max_new_tokens=4)
        assert s.wait(60) and s.status == "OK", (s.status, s.error)
        with pytest.raises(ValueError, match="sp must be >= 1"):
            r.load_decode("lm2", _fleet_factory(1), sp=0)
    finally:
        r.stop()


def test_fleet_tp_footprint_and_headroom_not_double_counted():
    from mxnet_tpu.serving.fleet import FleetRouter
    r = FleetRouter(replicas=1, failover_budget=2)
    try:
        r.load_decode("lm", _fleet_factory(2), replicas=1, tp=2)
        assert r.wait_converged(10)
        adv = r.scaling_advice()
        assert adv["devices_in_use"] == 2
        assert adv["devices_total"] == 8
        rid = r.stats()["decode_models"]["lm"]["placement"][0]
        sig2 = r.engine("lm", rid).routing_signals()
        assert sig2["tp_degree"] == 2
    finally:
        r.stop()
    r1 = FleetRouter(replicas=1, failover_budget=2)
    try:
        r1.load_decode("lm", _fleet_factory(1), replicas=1)
        assert r1.wait_converged(10)
        assert r1.scaling_advice()["devices_in_use"] == 1
        rid = r1.stats()["decode_models"]["lm"]["placement"][0]
        sig1 = r1.engine("lm", rid).routing_signals()
        # the pool is head-SHARDED, not replicated: logical kv headroom is
        # identical across tp degrees — summing placements never counts a
        # block once per shard
        assert sig1["kv_capacity"] == sig2["kv_capacity"]
        assert sig1["kv_blocks_free"] == sig2["kv_blocks_free"]
    finally:
        r1.stop()


def test_fleet_tp_mismatch_fails_load_and_rolls_back():
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.serving.fleet import FleetRouter
    r = FleetRouter(replicas=1, failover_budget=2)
    try:
        with pytest.raises(MXNetError,
                           match="tp=2 but its factory built an engine "
                                 "with tp_degree=1"):
            r.load_decode("lm", _fleet_factory(1), replicas=1, tp=2)
        # the spec rolled back: the name is free for a corrected load
        r.load_decode("lm", _fleet_factory(2), replicas=1, tp=2)
        assert r.wait_converged(10)
        with pytest.raises(ValueError, match="tp must be >= 1"):
            r.load_decode("lm2", _fleet_factory(1), replicas=1, tp=0)
    finally:
        r.stop()


def test_tp_degree_counter_lands_in_profiler_dump(tmp_path):
    from mxnet_tpu import profiler
    trace = str(tmp_path / "shard_profile.json")
    profiler.set_config(filename=trace)
    profiler.set_state("run")
    try:
        eng = DecodeEngine(ShardedDecodeModel(TinyCausalLM(**_FLEET_CFG),
                                              tp=2),
                           name="shprof", **_FLEET_EKW)
        try:
            assert eng.stats_snapshot()["tp_degree"] == 2
            s = eng.submit([5, 3, 7], 4, timeout_ms=30000)
            assert s.result().status == OK
        finally:
            eng.stop()
    finally:
        profiler.set_state("stop")
        profiler.dump()
    events = json.load(open(trace))["traceEvents"]
    counters = {e["name"] for e in events if e.get("ph") == "C"}
    assert "shprof:tp_degree" in counters, counters


# ---------------------------------------------------------------------------
# chaos: the mxstress "sharded_decode" scenario (5 seeds, tier-1 budget)
# ---------------------------------------------------------------------------

def test_sharded_decode_chaos_five_seeds_zero_violations():
    from mxnet_tpu.analysis import schedule
    report = schedule.stress(seeds=schedule.FAULT_SMOKE_SEEDS,
                             scenarios=("sharded_decode",))
    flat = ["seed %s [%s] %s" % (seed, scen, v)
            for seed, per_seed in report["seeds"].items()
            for scen, violations in per_seed.items()
            for v in violations]
    assert report["violations"] == 0, "\n".join(flat)
    assert report["preemptions"] > 0        # the harness really perturbed


# ---------------------------------------------------------------------------
# serve_bench sharded-decode profile: the smoke's report and count gates
# ---------------------------------------------------------------------------

def test_serve_bench_sharded_decode_smoke_artifact(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import serve_bench
    out = str(tmp_path / "report.json")
    rc = serve_bench.main(["--smoke", "--profile", "sharded-decode",
                           "--out", out])
    assert rc == 0
    report = json.load(open(out))
    assert report["profile"] == "sharded-decode"
    streams = report["workload"]["streams"]
    for key in ("tp1", "tp2"):
        leg = report[key]
        assert leg["statuses"] == {"OK": streams}
        assert leg["token_equal_reference"] is True
        assert leg["steady_state_recompiles"] == 0
        assert leg["kv_leaked_blocks"] == 0
    assert report["tp1"]["devices"] == report["tp2"]["devices"]
    assert report["tp1"]["engines"] == report["workload"]["tp"]
    assert report["tp2"]["engines"] == 1
    assert report["tp2"]["tp_degree"] == report["workload"]["tp"]
    layers = report["workload"]["model"]["num_layers"]
    coll = report["collectives"]
    assert coll["gathers_per_step"] == 0
    assert coll["psums_per_step"] == 2 * layers + 2
    assert coll["static_matches_runtime"] is True
    assert coll["per_axis"]["psum"]["tp"]["calls"] == coll["psums_per_step"]
    assert coll["static_predicted"]["psum"]["calls"] == coll["psums_per_step"]
    assert coll["static_predicted"]["all_gather"] == {"calls": 0, "bytes": 0}
    mem = report["memory"]
    assert mem["static_matches_runtime"] is True
    assert mem["static_predicted_peak_bytes"] == mem["runtime_peak_bytes"] > 0
    assert mem["temps_per_step"] > 0 and mem["live_bytes_after"] == 0
    # the per-device throughput ratio is reported for a chip run, never
    # compared with a number here
    assert "relative_tokens_per_s" in report


# ---------------------------------------------------------------------------
# compute-parallel kernels: tp=4 parity, the allclose-logit envelope, and
# the eager canonical-schema validation
# ---------------------------------------------------------------------------

_TP4_KW = dict(vocab_size=32, hidden=16, num_layers=1, num_heads=4,
               max_len=48, seed=11)


def test_tp4_streams_token_identical_greedy_and_sampled():
    ref_eng = _engine(TinyCausalLM(**_TP4_KW), "tp4ref")
    eng = _engine(ShardedDecodeModel(TinyCausalLM(**_TP4_KW), tp=4),
                  "tp4sh")
    try:
        for kw in ({}, dict(_SAMPLE)):
            for p in _PROMPTS:
                want = ref_eng.generate_reference(p, 8, **kw).tolist()
                s = eng.submit(list(p), 8, timeout_ms=30000, **kw)
                assert s.result().status == OK
                assert list(s.tokens()) == want
        assert _leak(eng) == 0
    finally:
        ref_eng.stop()
        eng.stop()


def _prefill_logits(m, prompt, num_blocks=8, bs=4):
    """Raw last-position prefill logits (the engine-internal call shape:
    unwrapped jnp params and pools, one padded prompt row)."""
    import jax.numpy as jnp
    L = len(prompt)
    shape = (m.num_layers, num_blocks, bs, m.num_heads, m.head_dim)
    if hasattr(m, "zeros_pool"):
        kp, vp = m.zeros_pool(shape)._data, m.zeros_pool(shape)._data
    else:
        kp = vp = jnp.zeros(shape, jnp.float32)
    p = {n: a._data for n, a in m.param_dict().items()}
    tokens = jnp.asarray([list(prompt)], jnp.int32)
    length = jnp.asarray([L], jnp.int32)
    table = jnp.arange((L + bs - 1) // bs, dtype=jnp.int32)[None]
    logits, _, _ = m.prefill_fn(p, tokens, length, table, kp, vp)
    return np.asarray(logits)[0]


def test_sharded_logits_allclose_with_documented_root_cause(model,
                                                            sh_model):
    """The compute-parallel logits are allclose — NOT bitwise — to the
    single-device reference.  Root cause: each Megatron half-block
    reduces its row-parallel partial products with a psum, and the psum's
    member-order summation associates the hidden-axis contraction
    differently than the unsharded ``[S,H] @ [H,H]`` matmul; float
    addition is not associative, so the last mantissa bits drift
    (~1e-7 relative on the tiny model).  The serving bar is therefore
    token identity — argmax and the seeded sampler ride far above that
    noise — which the stream-level tests above pin bitwise."""
    ref = _prefill_logits(model, _PROMPT)
    got = _prefill_logits(sh_model, _PROMPT)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    assert int(np.argmax(got)) == int(np.argmax(ref))


def test_wire_and_context_attention_validation_is_eager():
    inner = TinyCausalLM(**_MODEL_KW)
    with pytest.raises(ValueError, match="unknown wire '4bit'"):
        ShardedDecodeModel(TinyCausalLM(**_MODEL_KW), tp=2, wire="4bit")
    with pytest.raises(ValueError, match="wire_threshold\\s*> 0"):
        ShardedDecodeModel(TinyCausalLM(**_MODEL_KW), tp=2, wire="2bit",
                           wire_threshold=0.0)
    inner.context_attention = "sp"
    with pytest.raises(ValueError, match="head-local attention"):
        ShardedDecodeModel(inner, tp=2)


class _ParamOverride:
    """Wrap a contract model but dictate its param_dict()."""

    def __init__(self, inner, mutate):
        self._inner = inner
        self._mutate = mutate

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def param_dict(self):
        params = dict(self._inner.param_dict())
        self._mutate(params)
        return params


def test_canonical_schema_validation_is_eager():
    import jax.numpy as jnp
    from mxnet_tpu.ndarray import NDArray

    def extra(params):
        params["l0_bias"] = params["l0_wq"]

    with pytest.raises(ValueError, match=r"unexpected \['l0_bias'\]"):
        ShardedDecodeModel(_ParamOverride(TinyCausalLM(**_MODEL_KW),
                                          extra), tp=2)

    def wrong_shape(params):
        params["pos"] = NDArray(jnp.zeros((4, 4), jnp.float32))

    with pytest.raises(ValueError, match=r"'pos' has shape \(4, 4\)"):
        ShardedDecodeModel(_ParamOverride(TinyCausalLM(**_MODEL_KW),
                                          wrong_shape), tp=2)

    from jax.sharding import PartitionSpec as P
    inner = TinyCausalLM(**_MODEL_KW)
    specs = dict(inner.partition_specs())
    specs["l0_wo"] = P(None, "tp")          # column where row is required
    with pytest.raises(ValueError,
                       match="Megatron kernels require \\('tp',\\)"):
        ShardedDecodeModel(_SpecOverride(inner, specs), tp=2)


# ---------------------------------------------------------------------------
# opt-in wire="2bit": codec exactness, accuracy envelope, wire-byte bill
# ---------------------------------------------------------------------------

def test_wire_2bit_psum_bitwise_at_representable_inputs():
    """At inputs the codec represents exactly — every element in
    ``{-thr, 0, +thr}`` with a power-of-two threshold — the quantized
    psum is BITWISE equal to the exact fp32 psum: the ±1 int8 codes
    reconstruct each member's contribution with zero residual."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.serving.decode import sharding as shd

    thr = 0.25
    geom = shd._Geometry(num_layers=1, num_heads=2, local_heads=1,
                         head_dim=8, hidden=16, hidden_local=8,
                         vocab_size=32, max_len=32, tp=2, gluon=False,
                         wire="2bit", wire_threshold=thr)
    mesh = decode_mesh(2)
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.choice([-thr, 0.0, thr], size=(2, 16)),
                    jnp.float32)
    quant = shard_map(lambda x: shd._psum_2bit(geom, x), mesh=mesh,
                      in_specs=P("tp"), out_specs=P("tp"),
                      check_vma=False)
    exact = shard_map(lambda x: jax.lax.psum(x, "tp"), mesh=mesh,
                      in_specs=P("tp"), out_specs=P("tp"),
                      check_vma=False)
    assert np.asarray(quant(y)).tobytes() == np.asarray(exact(y)).tobytes()


def test_wire_2bit_envelope_and_wire_byte_reduction():
    """End-to-end ``wire="2bit"`` serving accuracy + cost envelope:

    * the decode step stays gather-free with the same ``2L+2`` psum
      bill, but the two per-layer block psums carry 1-byte int8 codes —
      the counter bytes drop below the exact-wire bill and match the
      static predictor exactly;
    * logits stay finite and inside a LOOSE documented envelope of the
      exact-wire logits (the codec is lossy by design — sign information
      at ±threshold only; this is an opt-in accuracy/bandwidth trade,
      NOT token-identical serving);
    * the assembly and unembed psums stay exact fp32 (predictor terms).
    """
    import jax.numpy as jnp
    from mxnet_tpu.analysis.sharding_lint import (
        predict_decode_step_collectives)
    from mxnet_tpu.parallel.collectives import (collective_totals,
                                                reset_collective_counters)

    exact_m = ShardedDecodeModel(TinyCausalLM(**_MODEL_KW), tp=2)
    wire_m = ShardedDecodeModel(TinyCausalLM(**_MODEL_KW), tp=2,
                                wire="2bit", wire_threshold=0.05)
    S, W, bs = 2, 2, 4
    shape = (wire_m.num_layers, S * W + 1, bs, wire_m.num_heads,
             wire_m.head_dim)
    kp, vp = wire_m.zeros_pool(shape), wire_m.zeros_pool(shape)
    p = {n: a._data for n, a in wire_m.param_dict().items()}
    reset_collective_counters()
    logits, _, _ = wire_m.decode_fn(p, jnp.zeros((S,), jnp.int32),
                                    jnp.zeros((S,), jnp.int32),
                                    jnp.zeros((S, W), jnp.int32),
                                    kp._data, vp._data)
    totals = collective_totals()
    reset_collective_counters()
    predicted = predict_decode_step_collectives(wire_m, slots=S)
    exact_bill = predict_decode_step_collectives(exact_m, slots=S)
    layers = wire_m.num_layers
    assert totals.get("all_gather", {"calls": 0})["calls"] == 0
    assert totals["psum"]["calls"] == 2 * layers + 2
    assert totals["psum"]["calls"] == predicted["psum"]["calls"]
    assert totals["psum"]["bytes"] == predicted["psum"]["bytes"]
    # the two block psums shrink 4 bytes -> 1 byte per element; the
    # assembly + unembed psums stay fp32, so the delta is exactly the
    # block-psum elements times 3 bytes
    hidden = wire_m.num_heads * wire_m.head_dim
    assert (exact_bill["psum"]["bytes"] - predicted["psum"]["bytes"]
            == 2 * layers * S * hidden * 3)

    got = np.asarray(logits)
    ref = np.asarray(exact_m.decode_fn(
        p, jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32),
        jnp.zeros((S, W), jnp.int32),
        exact_m.zeros_pool(shape)._data,
        exact_m.zeros_pool(shape)._data)[0])
    assert np.all(np.isfinite(got))
    # documented loose envelope: the residual-free sign codec clamps
    # each block-psum element to +-tp*threshold, so logit error is
    # bounded but NOT small — this wire trades accuracy for bandwidth
    assert float(np.max(np.abs(got - ref))) < 16.0


def test_wire_2bit_streams_complete_ok():
    """A wire="2bit" engine still serves: fixed shapes, zero recompiles
    in steady state, zero leaks.  (Token identity is NOT claimed — the
    codec is lossy; only the serving invariants hold.)"""
    eng = _engine(ShardedDecodeModel(TinyCausalLM(**_MODEL_KW), tp=2,
                                     wire="2bit"), "sh2bit")
    try:
        s = eng.submit(list(_PROMPT), 8, timeout_ms=30000)
        assert s.result().status == OK
        before = eng.stats_snapshot()["cache"]["recompiles"]
        for p in _PROMPTS:
            s = eng.submit(list(p), 8, timeout_ms=30000)
            assert s.result().status == OK
            assert all(0 <= t < eng.model.vocab_size for t in s.tokens())
        assert eng.stats_snapshot()["cache"]["recompiles"] == before
        assert _leak(eng) == 0
    finally:
        eng.stop()
