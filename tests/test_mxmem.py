"""mxmem device-memory lint tests (analysis/memory_lint.py + the runtime
HBM-accountant twin in mxnet_tpu/memory_accounting.py).

Five contracts, all tier-1:

* every MEM rule fires on the known-bad fixture at exactly the marked
  line — donation resolved at runtime, undonated carry, use-after-donate,
  budget breach, hot-path alloc without reserve(), full-shape gather,
  tag hygiene — and stays quiet on the clean fixture (no false
  positives);
* the repo itself ships MEM-clean: ``--passes mem`` over mxnet_tpu/
  reports zero findings (empty baseline), every memory site carries a
  sanction, three regions declare hbm budgets, and docs/MEM_MAP.md
  matches a fresh render;
* the planted bad_memory fixture is caught BOTH statically (site
  inventory) and dynamically (byte-accountant deltas) against ONE
  ground truth — and ``predict_decode_step_peak_bytes()`` equals the
  measured decode-step peak of a real ``ShardedDecodeModel`` exactly;
* the accountant's ledger survives an adversarial schedule: the
  mxstress ``mem`` scenario holds conservation, mirroring, and the
  admission budget over the smoke seed set;
* the pass is registered (registry drift, CLI, --since auto-include)
  and both bench artifacts carry schema-complete memory sections.
"""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import pytest

from mxnet_tpu.analysis import common, memory_lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "data", "lint_fixtures")
MXLINT = os.path.join(REPO, "tools", "mxlint.py")
MEM_MAP = os.path.join(REPO, "docs", "MEM_MAP.md")


def _fixture(name):
    with open(os.path.join(FIXTURES, name)) as f:
        return f.read()


def _pairs(findings):
    return sorted((f.rule, f.line) for f in findings)


def _analyze(source, path="inline.py"):
    return memory_lint.analyze_source(textwrap.dedent(source), path)


def _load_fixture_module(name):
    spec = importlib.util.spec_from_file_location(
        name[:-3], os.path.join(FIXTURES, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_mxlint(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, MXLINT] + list(args),
        cwd=cwd, capture_output=True, text=True)


# ---------------------------------------------------------------------------
# rule-by-rule: the known-bad fixture, exact (rule, line) pins
# ---------------------------------------------------------------------------

def test_mem_rules_fire_at_marked_lines():
    findings = memory_lint.analyze_source(
        _fixture("bad_memory.py"), "bad_memory.py")
    assert _pairs(findings) == [
        ("MEM001", 26), ("MEM001", 30), ("MEM002", 38), ("MEM003", 42),
        ("MEM004", 52), ("MEM005", 62), ("MEM006", 70), ("MEM006", 72)]


def test_mem_messages_explain_the_fix():
    findings = memory_lint.analyze_source(
        _fixture("bad_memory.py"), "bad_memory.py")
    by = {(f.rule, f.line): f for f in findings}
    # the runtime-resolved donation names the hazard, not just the site
    assert "resolved at runtime" in by[("MEM001", 26)].message
    # the carry finding spells out the double-buffer cost
    assert "double" in by[("MEM001", 30)].message
    # the use-after-donate names the surrendered buffer
    assert "`state`" in by[("MEM002", 38)].message
    # the breach carries the concrete byte count and the declared cap
    assert "16384" in by[("MEM003", 42)].message
    assert "budget(hbm=4KB)" in by[("MEM003", 42)].message
    # the hot alloc is sized by the symbolic model (8x8 f32 = 256B)
    assert "256B" in by[("MEM004", 52)].message
    # the full-shape temp lands inside the shard_map body scope
    assert by[("MEM005", 62)].scope == "sharded_gather.body"


def test_clean_memory_fixture_stays_quiet():
    findings = memory_lint.analyze_source(
        _fixture("clean_memory.py"), "clean_memory.py")
    assert _pairs(findings) == []


def test_mem001_sanction_and_donation_round_trip():
    # an undonated carry is MEM001; a nodonate tag with a reason
    # sanctions it; donating (and binding a fresh name — the donated
    # input is dead) fixes it for real
    src = """\
    import jax

    def run(step0, state):
        step = jax.jit(step0)
        state = step(state)
        return state
    """
    assert _pairs(_analyze(src)) == [("MEM001", 4)]
    tagged = src.replace(
        "jax.jit(step0)",
        "jax.jit(step0)  # mxmem: nodonate(state is re-read by the host)")
    assert _pairs(_analyze(tagged)) == []
    donated = src.replace(
        "jax.jit(step0)", "jax.jit(step0, donate_argnums=(0,))").replace(
        "state = step(state)\n        return state",
        "new_state = step(state)\n        return new_state")
    assert _pairs(_analyze(donated)) == []


def test_mem003_symbolic_sizes_never_breach():
    # a variable dimension makes the size symbolic: the budget cannot
    # prove a breach and must stay quiet
    src = """\
    import jax.numpy as jnp

    # mxmem: budget(hbm=1KB)
    def run(n):
        return jnp.zeros((n, 64), jnp.float32)
    """
    assert _pairs(_analyze(src)) == []
    concrete = src.replace("(n, 64)", "(64, 64)")
    assert _pairs(_analyze(concrete)) == [("MEM003", 3)]


def test_mem004_reserve_coverage_through_the_owning_class():
    # the class defining reserve() is its own allocator: pool growth
    # inside it is admission-covered without a per-site call
    src = """\
    import numpy as np

    class Pool:
        def reserve(self, seq, n):
            return True

        # mxflow: hot
        def grow_storage(self):
            return np.zeros((8, 8), "float32")
    """
    assert _pairs(_analyze(src)) == []


# ---------------------------------------------------------------------------
# the repo ships MEM-clean, sanctioned, budgeted, with a fresh MEM_MAP
# ---------------------------------------------------------------------------

def test_repo_is_mem_clean():
    assert memory_lint.run(REPO) == []


def test_repo_memory_sites_all_sanctioned():
    sites = memory_lint.memory_sites(REPO)
    assert sites, "the runtime has memory sites"
    unsanctioned = [s for s in sites if s["sanction"] == "UNSANCTIONED"]
    assert unsanctioned == []
    # the engine's CachedOp carries are documented nodonate sites
    nodonate = [s for s in sites
                if s["path"] == "mxnet_tpu/serving/decode/engine.py"
                and s["sanction"] == "nodonate"]
    assert len(nodonate) >= 3
    assert all(s["reason"].strip() for s in nodonate)


def test_three_regions_declare_hbm_budgets():
    _sites, budgets = memory_lint.mem_map_entries(REPO)
    regions = {b["region"]: b for b in budgets}
    assert set(regions) == {
        "ShardedDecodeModel._build_fn.body",            # decode step
        "CompiledTrainStep._make_forward_fn.forward_fn",  # fit step
        "make_sharded_update_step.step.body",           # ZeRO update
    }
    for b in budgets:
        assert b["concrete_bytes"] <= b["cap_bytes"]
    # the training regions still cover their full-shape gather sites;
    # the compute-parallel decode step has NONE left (the deleted
    # gather tax — its temps are the 2L+2 psum outputs)
    assert regions["ShardedDecodeModel._build_fn.body"][
        "gather_sites"] == 0
    for qual in ("CompiledTrainStep._make_forward_fn.forward_fn",
                 "make_sharded_update_step.step.body"):
        assert regions[qual]["gather_sites"] >= 1


def test_mem_map_is_fresh():
    entries = memory_lint.mem_map_entries(REPO)
    sites, budgets = entries
    assert sites and budgets
    with open(MEM_MAP) as f:
        committed = f.read()
    assert committed == memory_lint.render_mem_map(entries), \
        "docs/MEM_MAP.md is stale: run `python tools/mxlint.py --mem-map`"


# ---------------------------------------------------------------------------
# the twin contract: static site inventory == runtime accountant deltas
# ---------------------------------------------------------------------------

def test_memory_fixture_caught_statically_and_dynamically():
    from mxnet_tpu.memory_accounting import (memory_counters,
                                             reset_memory_counters,
                                             track_region)
    src = _fixture("bad_memory.py")
    static = memory_lint.site_counts(
        memory_lint.source_memory_sites(src, "bad_memory.py"))
    mod = _load_fixture_module("bad_memory.py")
    gt = mod.GROUND_TRUTH
    assert static == gt["sites"]
    reset_memory_counters()
    try:
        with track_region("fixture:set"):
            mod.drive()
        snap = memory_counters()["fixture:set"]
    finally:
        reset_memory_counters()
    # temps are allocations too (batch-freed at scope exit), so the
    # alloc/free/byte columns carry the instrumented sites PLUS the
    # collective wrapper's output temp
    assert snap["temps"] == gt["temps"]
    assert snap["allocs"] == gt["allocs"] + gt["temps"]
    assert snap["frees"] == gt["frees"] + gt["temps"]
    assert snap["alloc_bytes"] == gt["alloc_bytes"] + gt["temp_bytes"]
    assert snap["peak_bytes"] == gt["peak_bytes"]
    assert snap["live_bytes"] == 0


def test_accountant_ledger_and_reset_api():
    from mxnet_tpu import memory_accounting as ma
    ma.reset_memory_counters()
    try:
        ma.record_alloc(1000, "t:a")
        ma.record_alloc(500, "t:a")
        ma.record_free(1000, "t:a")
        snap = ma.memory_counters()["t:a"]
        assert snap["allocs"] == 2 and snap["frees"] == 1
        assert snap["alloc_bytes"] == 1500
        assert snap["live_bytes"] == 500
        assert snap["peak_bytes"] == 1500       # no-reuse worst case
        assert ma.region_peak_bytes("t:a") == 1500
        totals = ma.memory_totals()
        assert totals["alloc_bytes"] == 1500
        # the snapshot is a copy: later resets must not mutate it
        ma.reset_memory_counters()
        assert ma.memory_counters() == {}
        assert snap["alloc_bytes"] == 1500
    finally:
        ma.reset_memory_counters()


def test_track_region_scopes_nest_and_temps_batch_free():
    from mxnet_tpu import memory_accounting as ma
    # no active scope: record_temp is a no-op that reports it did nothing
    assert ma.record_temp(64) is False
    assert ma.current_region() is None
    ma.reset_memory_counters()
    try:
        with ma.track_region("t:outer"):
            assert ma.current_region() == "t:outer"
            assert ma.record_temp(64) is True
            with ma.track_region("t:inner"):
                assert ma.current_region() == "t:inner"
                assert ma.record_temp(16) is True
            # inner temps freed at inner scope exit
            inner = ma.memory_counters()["t:inner"]
            assert inner["temps"] == 1 and inner["live_bytes"] == 0
            assert ma.current_region() == "t:outer"
        outer = ma.memory_counters()["t:outer"]
        assert outer["temps"] == 1
        assert outer["alloc_bytes"] == outer["freed_bytes"] == 64
        assert outer["live_bytes"] == 0 and outer["peak_bytes"] == 64
    finally:
        ma.reset_memory_counters()


def test_profiler_counters_gate_on_active_session():
    from mxnet_tpu import memory_accounting as ma
    from mxnet_tpu import profiler
    ma.reset_memory_counters()
    try:
        ma.record_alloc(128, "t:prof")
        # no profiling session: the live-bytes Counter writers must not
        # run (Counter.set_value appends trace events unconditionally —
        # an unbounded buffer in a long-lived server)
        assert ma._PROF_COUNTERS == {}
        profiler.set_state("run")
        ma.record_alloc(128, "t:prof")
        assert "t:prof" in ma._PROF_COUNTERS
        counter = ma._PROF_COUNTERS["t:prof"]
        assert counter._value == ma.memory_counters()["t:prof"][
            "live_bytes"]
    finally:
        profiler.set_state("stop")
        ma.reset_memory_counters()


# ---------------------------------------------------------------------------
# the decode-step acceptance cross-check (static model == metered truth)
# ---------------------------------------------------------------------------

def test_decode_step_peak_prediction_matches_runtime():
    import jax.numpy as jnp
    from mxnet_tpu.memory_accounting import (memory_counters,
                                             reset_memory_counters,
                                             track_region)
    from mxnet_tpu.serving.decode import ShardedDecodeModel, TinyCausalLM

    model = ShardedDecodeModel(
        TinyCausalLM(vocab_size=32, hidden=16, num_layers=1, num_heads=2,
                     max_len=48, seed=3), tp=2)
    S, W, bs = 2, 2, 4
    pool_shape = (model.num_layers, S * W + 1, bs, model.num_heads,
                  model.head_dim)
    k_pool = model.zeros_pool(pool_shape)
    v_pool = model.zeros_pool(pool_shape)
    p = {n: a._data for n, a in model.param_dict().items()}
    reset_memory_counters()
    try:
        with track_region("test:decode-step"):
            model.decode_fn(p, jnp.zeros((S,), jnp.int32),
                            jnp.zeros((S,), jnp.int32),
                            jnp.zeros((S, W), jnp.int32),
                            k_pool._data, v_pool._data)
        region = memory_counters()["test:decode-step"]
    finally:
        reset_memory_counters()
    predicted = memory_lint.predict_decode_step_peak_bytes(
        model, slots=S)
    # exact agreement — the abstract footprint model is the metered
    # truth of the psum-output temps, not an estimate (the gathered
    # weight/pool temps of the PR 15 wrapper no longer exist)
    assert predicted == region["peak_bytes"] > 0
    assert region["live_bytes"] == 0            # all temps drained
    # 2L+2 psum outputs are the ONLY collective temps per decode step
    assert region["temps"] == 2 * model.num_layers + 2


# ---------------------------------------------------------------------------
# the KV-block accountant: engine hooks and byte-based headroom
# ---------------------------------------------------------------------------

def test_kv_cache_mirrors_block_ledger_in_bytes():
    from mxnet_tpu.memory_accounting import (memory_counters,
                                             reset_memory_counters)
    from mxnet_tpu.serving.decode.kv_cache import PagedKVCache
    reset_memory_counters()
    try:
        cache = PagedKVCache(2, 9, 4, 2, 4, account_region="t:kv")
        assert cache.stats()["block_bytes"] == cache.block_bytes == \
            2 * 2 * 4 * 2 * 4 * 4
        assert cache.reserve("s", 3)
        cache.ensure_capacity("s", 9)           # 3 blocks attached
        cache.free_seq("s")
        stats = cache.stats()
        assert stats["allocated_total"] == stats["freed_total"] == 3
        snap = memory_counters()["t:kv"]
        assert snap["allocs"] == snap["frees"] == 3
        assert snap["alloc_bytes"] == 3 * cache.block_bytes
        assert snap["live_bytes"] == 0
        assert snap["peak_bytes"] == 3 * cache.block_bytes
    finally:
        reset_memory_counters()


def test_routing_signals_and_scaling_advice_carry_bytes():
    from mxnet_tpu.serving.decode import DecodeEngine, TinyCausalLM
    from mxnet_tpu.serving.fleet import FleetRouter

    def factory(name):
        return DecodeEngine(
            TinyCausalLM(vocab_size=20, hidden=16, num_layers=1,
                         num_heads=2, max_len=24, seed=13),
            name=name, max_slots=2, block_size=4, num_blocks=9,
            max_prompt_len=4, max_new_tokens=5, max_queue=6,
            width_blocks=[4])

    router = FleetRouter(replicas=1, failover_budget=2)
    try:
        router.load_decode("lm", factory, replicas=1)
        assert router.wait_converged(10)
        rid = router.stats()["decode_models"]["lm"]["placement"][0]
        sig = router.engine("lm", rid).routing_signals()
        bb = sig["kv_block_bytes"]
        assert bb > 0
        assert sig["kv_bytes_free"] == sig["kv_blocks_free"] * bb
        assert sig["kv_bytes_capacity"] == sig["kv_capacity"] * bb
        assert sig["kv_bytes_live"] >= 0 and sig["kv_bytes_peak"] >= 0
        advice = router.scaling_advice()
        assert advice["kv_bytes_capacity"] == sig["kv_bytes_capacity"]
        assert advice["kv_bytes_free"] == sig["kv_bytes_free"]
        per_name = advice["engines"]["lm"]
        assert per_name["kv_bytes_capacity"] == sig["kv_bytes_capacity"]
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# chaos: the mxstress "mem" scenario (smoke seeds, tier-1 budget)
# ---------------------------------------------------------------------------

def test_mxstress_mem_scenario_zero_violations():
    from mxnet_tpu.analysis import schedule
    report = schedule.stress(seeds=schedule.FAULT_SMOKE_SEEDS,
                             scenarios=("mem",))
    flat = ["seed %s [%s] %s" % (seed, scen, v)
            for seed, per_seed in report["seeds"].items()
            for scen, violations in per_seed.items()
            for v in violations]
    assert report["violations"] == 0, "\n".join(flat)
    assert report["preemptions"] > 0        # the harness really perturbed


# ---------------------------------------------------------------------------
# registration: registry, CLI, --since auto-include, bench schema
# ---------------------------------------------------------------------------

def test_mem_pass_is_registered():
    assert "mem" in common.PASS_REGISTRY
    assert common.RULE_FAMILY_PASS["MEM"] == "mem"
    runner = common.resolve_runner("mem")
    assert runner is memory_lint.run
    assert common.pass_of_key("MEM001|a.py|f|d") == "mem"


def test_cli_mem_pass_clean():
    proc = _run_mxlint("--passes", "mem")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


def test_since_mode_auto_includes_mem(tmp_path):
    pkg = tmp_path / "mxnet_tpu"
    par = pkg / "parallel"
    par.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (par / "__init__.py").write_text("")
    (par / "base0.py").write_text("def helper(x):\n    return x\n")
    root = str(tmp_path)
    subprocess.run(["git", "init", "-q"], cwd=root, check=True)
    subprocess.run(["git", "add", "-A"], cwd=root, check=True)
    subprocess.run(["git", "-c", "user.name=t", "-c", "user.email=t@t",
                    "commit", "-qm", "seed"], cwd=root, check=True)

    # nothing under the scanned dirs changed: the mem pass is skipped
    proc = _run_mxlint("--root", root, "--since", "HEAD",
                       "--passes", "mem", "--no-baseline", "--json")
    assert proc.returncode == 0, proc.stderr
    assert json.loads(proc.stdout)["findings"] == []

    # an untracked parallel/ file with an undonated carry: the pass
    # runs, and its findings bypass the changed-file filter
    (par / "new_step.py").write_text(
        "import jax\n"
        "def run(step0, state):\n"
        "    step = jax.jit(step0)\n"
        "    state = step(state)\n"
        "    return state\n")
    proc = _run_mxlint("--root", root, "--since", "HEAD",
                       "--passes", "mem", "--no-baseline", "--json")
    assert proc.returncode == 1, proc.stderr
    found = json.loads(proc.stdout)["findings"]
    assert [f["rule"] for f in found] == ["MEM001"]
    assert found[0]["path"] == "mxnet_tpu/parallel/new_step.py"


def test_ci_lint_runs_mem():
    with open(os.path.join(REPO, "tools", "ci_lint.sh")) as f:
        script = f.read()
    assert "mem" in script or "--passes" not in script, \
        "ci_lint.sh must run the mem pass (default pass list covers it)"
