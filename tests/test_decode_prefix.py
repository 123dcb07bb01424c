"""Prefix caching + chunked prefill + sampling + speculative decode
(docs/SERVING.md "Prefix cache & speculative decode").

Tier-1 gates for the decode-throughput tentpole:

* **Copy-on-write prefix cache** — ``PagedKVCache`` chain-hashes prompt
  blocks; a later request attaches the longest registered prefix and
  forks a shared page only on its first divergent write.  Unit gates:
  fork-on-divergence, release decrements-not-frees, non-block-aligned
  partial prefixes can never hit, eviction never reclaims a page with
  live references.
* **Engine integration** — chunked + prefix-cached streams stay bitwise
  equal to ``generate_reference``, hits skip prefill chunks, a full
  duplicate of a live donor forks on the recomputed tail chunk, and the
  leak gate covers shared/CoW pages.
* **Speculative decode** — greedy output through the draft/verify path is
  bitwise-equal to the non-speculative sequential reference even with an
  independently-seeded (low-acceptance) draft.
* **Seeded sampling** — a sampled stream equals its sampled reference and
  replays across engine restarts; without an explicit seed the stream is
  still deterministic under ``mx.random.seed``.
* **Handoff** — a migrated stream carries refcounted shared pages and
  in-flight sampler state bitwise (the mxstress ``decode_prefix``
  scenario holds this under chaos over FAULT_SMOKE_SEEDS).
* **Bench** — ``serve_bench --profile prefix-spec`` (smoke) meets its
  count gates (prefix hits, fewer prefill chunks, accepted drafts).
"""
import json
import os
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.serving import OK
from mxnet_tpu.serving.decode import DecodeEngine, PagedKVCache, \
    TinyCausalLM

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROMPT = [5, 3, 7, 1, 2, 6, 4, 8]          # two 4-token blocks
_MODEL_KW = dict(vocab_size=32, hidden=16, num_layers=1, num_heads=2,
                 max_len=48, seed=3)


@pytest.fixture(scope="module")
def model():
    return TinyCausalLM(**_MODEL_KW)


@pytest.fixture(scope="module")
def draft():
    # same vocab, independent seed: proposals mostly DISAGREE with the
    # target, so acceptance is low — the parity gate must hold anyway
    kw = dict(_MODEL_KW)
    kw["seed"] = 99
    return TinyCausalLM(**kw)


def _engine(model, name, **over):
    kw = dict(max_slots=4, block_size=4, num_blocks=24, max_prompt_len=16,
              max_new_tokens=10, prefill_chunk=4, prefix_cache=True)
    kw.update(over)
    return DecodeEngine(model, name=name, **kw)


def _leak(engine):
    kv = engine.kv_stats()
    return kv["allocated_total"] - kv["freed_total"]


# ---------------------------------------------------------------------------
# PagedKVCache copy-on-write unit gates
# ---------------------------------------------------------------------------

def _cache(num_blocks=12):
    return PagedKVCache(num_layers=1, num_blocks=num_blocks, block_size=4,
                        num_heads=2, head_dim=8)


def _seed_donor(cache, seq_id, prompt):
    """Materialize + register ``prompt`` for ``seq_id`` (host accounting
    only — the unit gates never touch device pools)."""
    cache.reserve(seq_id, cache.blocks_for_tokens(len(prompt) + 4))
    cache.ensure_capacity(seq_id, len(prompt))
    cache.register_prefix(seq_id, prompt)


def test_cow_fork_on_divergent_write():
    cache = _cache()
    _seed_donor(cache, "a", _PROMPT)
    res = cache.reserve("b", cache.blocks_for_tokens(len(_PROMPT) + 4),
                        prompt=_PROMPT, align_tokens=4)
    assert res.full_hit and res.shared_blocks == 2
    assert res.prefix_tokens == 4           # tail chunk always recomputed
    shared = cache.blocks_of("a")
    assert cache.blocks_of("b") == shared   # same physical pages
    # first divergent write to the shared tail block forks it
    new, old = cache.writable("b", 1)
    assert old == shared[1] and new != old
    assert cache.blocks_of("a")[1] == old   # donor keeps the original
    assert cache.blocks_of("b")[1] == new
    assert cache.ref_count(old) == 1 and cache.ref_count(new) == 1
    assert cache.stats()["cow_forks"] == 1
    # refcount back to 1: the donor now writes its page in place
    blk, copy_src = cache.writable("a", 1)
    assert blk == old and copy_src is None


def test_release_of_shared_block_decrements_not_frees():
    cache = _cache()
    _seed_donor(cache, "a", _PROMPT)
    cache.reserve("b", cache.blocks_for_tokens(len(_PROMPT) + 4),
                  prompt=_PROMPT, align_tokens=4)
    shared = cache.blocks_of("a")
    assert cache.ref_count(shared[0]) == 2
    cache.free_seq("b")
    # the donor still owns the page: decremented, not reclaimed
    assert cache.ref_count(shared[0]) == 1
    assert cache.blocks_of("a") == shared
    cache.free_seq("a")
    stats = cache.stats()
    # registered pages park in the reusable cache, nothing leaks
    assert stats["cached_blocks"] == 2
    assert stats["used"] == 0
    assert stats["allocated_total"] == stats["freed_total"]


def test_partial_non_block_aligned_prefix_is_a_miss():
    cache = _cache()
    donor = _PROMPT[:6]                      # one full block + 2-token tail
    _seed_donor(cache, "a", donor)
    # shares 5 tokens (mid-block divergence): only the full first block
    # can attach — the partial tail is keyed by the EXACT full prompt, so
    # a merely-overlapping prefix can never collide into it
    res = cache.reserve("b", 4, prompt=donor[:5] + [29, 29, 29],
                        align_tokens=4)
    assert not res.full_hit
    assert res.prefix_tokens == 4 and res.shared_blocks == 1
    # the exact donor prompt DOES hit its registered tail block
    res = cache.reserve("c", 4, prompt=list(donor), align_tokens=4)
    assert res.full_hit and res.shared_blocks == 2
    assert res.prefix_tokens == 4


def test_eviction_never_reclaims_live_shared_pages():
    cache = _cache(num_blocks=5)             # 4 allocatable
    _seed_donor(cache, "a", _PROMPT)         # 2 registered blocks
    cache.free_seq("a")                      # ... parked in the LRU cache
    res = cache.reserve("b", 3, prompt=_PROMPT, align_tokens=4)
    assert res.shared_blocks == 2            # revived from the cache
    held = cache.blocks_of("b")
    # the pool cannot promise past free + evictable-cached - reserved:
    # b's live pages are NOT evictable, so this reservation must shed
    assert cache.reserve("c", 3) is False
    assert cache.blocks_of("b") == held
    cache.free_seq("b")
    # with b gone the pages are ref==0 cached again — now a plain
    # allocation may evict them (LRU, registry entries dropped)
    assert cache.reserve("c", 4) is True
    cache.ensure_capacity("c", 16)
    stats = cache.stats()
    assert stats["evictions"] >= 2
    cache.free_seq("c")                      # unregistered pages free fully
    res = cache.reserve("d", 1, prompt=_PROMPT, align_tokens=4)
    assert res.shared_blocks == 0            # registry gone with the pages


# ---------------------------------------------------------------------------
# engine integration: chunked prefill + prefix hits, bitwise
# ---------------------------------------------------------------------------

def test_chunked_prefix_streams_bitwise_equal_reference(model):
    eng = _engine(model, "px")
    try:
        assert eng.warmup_report["compiles"] == eng.warmup_report[
            "signatures"]
        miss0 = eng.cache_stats()["misses"]
        prompts = [list(_PROMPT), list(_PROMPT) + [9, 2],
                   list(_PROMPT) + [11, 3, 5, 7]]
        refs = [eng.generate_reference(p, 8) for p in prompts]
        # donor completes first so its prefix is registered for the rest
        donor = eng.submit(prompts[0], 8).result()
        assert donor.status == OK
        assert list(donor.tokens()) == refs[0].tolist()
        streams = [eng.submit(p, 8) for p in prompts[1:]]
        for stream, ref in zip(streams, refs[1:]):
            stream.result()
            assert stream.status == OK
            assert list(stream.tokens()) == ref.tolist()
        snap = eng.stats_snapshot()
        assert snap["prefix_hits"] >= 2
        assert snap["prefix_blocks_shared"] >= 4    # 2 blocks x 2 hits
        assert eng.cache_stats()["misses"] == miss0  # zero steady-state
        assert _leak(eng) == 0
    finally:
        eng.stop()
    assert _leak(eng) == 0                   # incl. shared/cached pages


def test_full_prompt_duplicate_forks_on_recompute(model):
    eng = _engine(model, "pxdup")
    try:
        donor = eng.submit(list(_PROMPT), 6).result()
        assert donor.status == OK
        ref = eng.generate_reference(list(_PROMPT), 6)
        # a longer-lived holder attaches the registered pages and holds
        # their refcount while the duplicate attaches behind it: the
        # recomputed tail chunk hits a shared page and must fork
        holder = eng.submit(list(_PROMPT), 10)
        dup = eng.submit(list(_PROMPT), 6)
        assert dup.result().status == OK
        assert holder.result().status == OK
        assert list(dup.tokens()) == ref.tolist()
        assert list(holder.tokens())[:len(ref)] == ref.tolist()
        snap = eng.stats_snapshot()
        assert snap["cow_forks"] >= 1
        assert snap["prefix_hits"] >= 2
        assert _leak(eng) == 0
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# speculative decode: greedy bitwise parity with an independent draft
# ---------------------------------------------------------------------------

def test_spec_greedy_bitwise_parity_with_independent_draft(model, draft):
    eng = _engine(model, "sp", spec_k=3, draft_model=draft)
    try:
        miss0 = eng.cache_stats()["misses"]
        prompts = [list(_PROMPT), list(_PROMPT) + [9, 2], [4, 4, 11]]
        refs = [eng.generate_reference(p, 10) for p in prompts]
        streams = [eng.submit(p, 10) for p in prompts]
        for stream, ref in zip(streams, refs):
            stream.result()
            assert stream.status == OK
            # speculation changes how many verify rows COMMIT per
            # dispatch, never their logits: output is bitwise-sequential
            assert list(stream.tokens()) == ref.tolist()
        snap = eng.stats_snapshot()
        assert snap["spec_proposed"] > 0
        assert 0 <= snap["spec_accepted"] <= snap["spec_proposed"]
        assert eng.cache_stats()["misses"] == miss0
        assert _leak(eng) == 0
    finally:
        eng.stop()


def test_self_draft_acceptance_is_high(model):
    # draft == target weights: proposals mostly agree under greedy, so
    # rounds commit multiple tokens (the dispatch-amortization the bench
    # measures) — and the output is still the sequential reference.  The
    # rate is high rather than exactly 1.0: proposals come from the
    # draft's [S, K] kernel and verification from the [S, K+1] kernel,
    # so near-tie argmaxes may legitimately differ per shape
    eng = _engine(model, "spself", spec_k=3, draft_model=model)
    try:
        ref = eng.generate_reference(list(_PROMPT), 10)
        stream = eng.submit(list(_PROMPT), 10).result()
        assert stream.status == OK
        assert list(stream.tokens()) == ref.tolist()
        snap = eng.stats_snapshot()
        assert snap["spec_accept_rate"] >= 0.5
        assert snap["spec_accepted"] >= 1
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# seeded sampling: replayable, restart-stable, mx.random-derived
# ---------------------------------------------------------------------------

def test_sampled_stream_matches_reference_and_replays_across_restart(
        model, draft):
    kw = dict(temperature=0.9, top_k=8, top_p=0.95, seed=1234)
    eng = _engine(model, "sam", spec_k=3, draft_model=draft)
    try:
        ref = eng.generate_reference(list(_PROMPT), 10, **kw)
        stream = eng.submit(list(_PROMPT), 10, **kw).result()
        assert stream.status == OK
        assert list(stream.tokens()) == ref.tolist()
        first = list(stream.tokens())
    finally:
        eng.stop()
    # same (prompt, params, seed) on a FRESH engine replays bitwise
    eng = _engine(model, "sam2", spec_k=3, draft_model=draft)
    try:
        replay = eng.submit(list(_PROMPT), 10, **kw).result()
        assert replay.status == OK
        assert list(replay.tokens()) == first
    finally:
        eng.stop()


def test_derived_seed_deterministic_under_framework_seed(model):
    eng = _engine(model, "samder")
    try:
        # no explicit seed: the effective seed derives from the CALLER's
        # framework RNG at submit() time, so re-seeding replays the stream
        mx.random.seed(21)
        one = eng.submit(list(_PROMPT), 8, temperature=0.7).result()
        mx.random.seed(21)
        two = eng.submit(list(_PROMPT), 8, temperature=0.7).result()
        assert one.status == OK and two.status == OK
        assert list(one.tokens()) == list(two.tokens())
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# handoff: shared pages + in-flight sampler state migrate bitwise
# ---------------------------------------------------------------------------

def test_handoff_carries_shared_pages_and_sampler_state(model):
    a = _engine(model, "ha", max_slots=2, max_new_tokens=16)
    b = _engine(model, "hb", max_slots=2, max_new_tokens=16)
    prompt = list(_PROMPT) + [9, 2]
    try:
        ref = a.generate_reference(prompt, 12)
        ref_sam = a.generate_reference(prompt, 12, temperature=0.8,
                                       seed=555)
        # donor registers the prefix; the next two attach shared pages
        assert a.submit(prompt, 12).result().status == OK
        greedy = a.submit(prompt, 12)
        sampled = a.submit(prompt, 12, temperature=0.8, seed=555)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            st_g, toks_g, _, _, _ = greedy.snapshot()
            st_s, toks_s, _, _, _ = sampled.snapshot()
            if (st_g is not None or len(toks_g) >= 3) and \
                    (st_s is not None or len(toks_s) >= 3):
                break
            time.sleep(0.005)
        assert a.quiesce()
        moved = a.export_streams()
        a.resume()
        for stream, snap in moved:
            stream.set_owner("mig")
            b.import_stream(snap, stream=stream, owner="mig")
        assert greedy.result().status == OK
        assert sampled.result().status == OK
        assert list(greedy.tokens()) == ref.tolist()
        # the importer continues the EXACT uniform draw sequence
        assert list(sampled.tokens()) == ref_sam.tolist()
        assert _leak(a) == 0
    finally:
        a.stop()
        b.stop()
    assert _leak(b) == 0


# ---------------------------------------------------------------------------
# chaos: the mxstress "decode_prefix" scenario (5 seeds, tier-1 budget)
# ---------------------------------------------------------------------------

def test_decode_prefix_chaos_five_seeds_zero_violations():
    from mxnet_tpu.analysis import schedule
    report = schedule.stress(seeds=schedule.FAULT_SMOKE_SEEDS,
                             scenarios=("decode_prefix",))
    flat = ["seed %s [%s] %s" % (seed, scen, v)
            for seed, per_seed in report["seeds"].items()
            for scen, violations in per_seed.items()
            for v in violations]
    assert report["violations"] == 0, "\n".join(flat)
    assert report["preemptions"] > 0        # the harness really perturbed


# ---------------------------------------------------------------------------
# serve_bench prefix-spec profile: the smoke's report and count gates
# ---------------------------------------------------------------------------

def test_serve_bench_prefix_spec_smoke_artifact(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import serve_bench
    out = str(tmp_path / "report.json")
    rc = serve_bench.main(["--smoke", "--profile", "prefix-spec",
                           "--out", out])
    assert rc == 0
    report = json.load(open(out))
    assert report["profile"] == "prefix-spec"
    streams = report["workload"]["streams"]
    for leg in ("baseline", "optimized"):
        snap = report[leg]
        assert snap["statuses"] == {"OK": streams}
        assert snap["steady_state_recompiles"] == 0
        assert snap["kv_leaked_blocks"] == 0
    opt = report["optimized"]
    assert opt["prefix_hits"] >= 1
    assert opt["full_prompt_prefills"] < streams
    assert opt["prefill_chunks"] < report["baseline"]["prefill_chunks"]
    assert opt["spec_proposed"] >= 1 and opt["spec_accepted"] >= 1
