"""mxshard sharding-lint tests (analysis/sharding_lint.py + the runtime
collective-counter twin in parallel/collectives.py).

Five contracts, all tier-1:

* every SPD rule fires on the known-bad fixture at exactly the marked
  line — including SPD004 through the ``partition_specs()`` indirection —
  and stays quiet on the clean fixture (no false positives);
* the repo itself ships SPD-clean: ``--passes spd`` over mxnet_tpu/
  reports zero findings (empty baseline), every collective site carries
  a justification, and docs/COLLECTIVE_MAP.md matches a fresh render;
* the planted bad_sharding fixture is caught BOTH statically (site
  inventory) and dynamically (runtime counter deltas) against ONE
  ground truth — the twin detectors must agree, on the fixture AND on a
  real ``ShardedDecodeModel`` decode step (calls and bytes);
* the SPD004 fixes are real: ulysses / ring / moe reject indivisible
  extents eagerly with ValueErrors naming both extents;
* the pass is registered (registry drift, CLI, --since auto-include)
  and the bench artifact carries the schema-complete collective bill.
"""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from mxnet_tpu.analysis import common, sharding_lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "data", "lint_fixtures")
MXLINT = os.path.join(REPO, "tools", "mxlint.py")
COLLECTIVE_MAP = os.path.join(REPO, "docs", "COLLECTIVE_MAP.md")


def _fixture(name):
    with open(os.path.join(FIXTURES, name)) as f:
        return f.read()


def _pairs(findings):
    return sorted((f.rule, f.line) for f in findings)


def _analyze(source, path="inline.py"):
    return sharding_lint.analyze_source(textwrap.dedent(source), path)


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

def test_spd_rules_fire_at_marked_lines():
    findings = sharding_lint.analyze_source(
        _fixture("bad_sharding.py"), "bad_sharding.py")
    assert _pairs(findings) == [
        ("SPD001", 33), ("SPD002", 36), ("SPD003", 20), ("SPD003", 28),
        ("SPD004", 42), ("SPD005", 55), ("SPD006", 53), ("SPD007", 63),
        ("SPD007", 65)]


def test_spd_messages_explain_the_fix():
    findings = sharding_lint.analyze_source(
        _fixture("bad_sharding.py"), "bad_sharding.py")
    by = {(f.rule, f.line): f for f in findings}
    # the gather is flagged as compute-feeding (the x @ full taint)
    assert "feeds a contraction" in by[("SPD001", 33)].message
    # the breach names the region and its declared budget
    assert "budget(psum=1)" in by[("SPD002", 36)].message
    assert by[("SPD002", 36)].scope == "block"
    # SPD004 anchors on the shard_map construction, names the body region
    assert "`block`" in by[("SPD004", 42)].message
    # the loop-carry finding lands inside the fori_loop body
    assert by[("SPD006", 53)].scope == "scan_reshard.shifted.body"


def test_clean_sharding_fixture_stays_quiet():
    findings = sharding_lint.analyze_source(
        _fixture("clean_sharding.py"), "clean_sharding.py")
    assert _pairs(findings) == []


def test_spd004_propagates_through_spec_indirection():
    # the P("tp") literal lives in a helper the shard_map call names —
    # the lint must chase the indirection to see the sharded in_spec
    src = """\
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from mxnet_tpu.parallel.collectives import allreduce

    def make_mesh(devs):
        return Mesh(devs, ("tp",))

    def specs():
        return (P(None, "tp"),)

    def body(x):
        return allreduce(x, "tp")  # mxshard: reduce-ok(fixture sum)

    def run(mesh, x):
        fn = shard_map(body, mesh=mesh, in_specs=specs(), out_specs=P())
        return fn(x)
    """
    assert _pairs(_analyze(src)) == [("SPD004", 15)]
    guarded = src.replace(
        "    def run(mesh, x):\n",
        "    def run(mesh, x):\n"
        "        if x.shape[0] % 2:\n"
        "            raise ValueError('extent %d vs tp 2' % x.shape[0])\n")
    assert _pairs(_analyze(guarded)) == []


def test_spd003_axis_resolution_through_locals():
    # the axis rides a local assignment; the lint resolves it and checks
    # it against the declared universe
    src = """\
    from jax.sharding import Mesh
    from mxnet_tpu.parallel.collectives import allreduce

    def make_mesh(devs):
        return Mesh(devs, ("tp",))

    def run(x):
        ax = "nope"
        return allreduce(x, ax)
    """
    # the resolved axis is unknown AND the declared axis goes unused
    assert _pairs(_analyze(src)) == [("SPD003", 5), ("SPD003", 9)]


# ---------------------------------------------------------------------------
# the repo ships SPD-clean, annotated, with a fresh COLLECTIVE_MAP
# ---------------------------------------------------------------------------

def test_repo_is_spd_clean():
    assert sharding_lint.run(REPO) == []


def test_repo_collective_sites_all_sanctioned():
    sites = sharding_lint.collective_sites(REPO)
    assert sites, "the parallel kernels perform collectives"
    unsanctioned = [s for s in sites if s["sanction"] == "UNSANCTIONED"]
    assert unsanctioned == []
    assert all(s["reason"].strip() for s in sites)
    # the gather tax is DELETED: the compute-parallel kernels keep zero
    # gather-ok sites in the decode-step region (the only sharding.py
    # all_gather left is the fused long-context sp path, outside it)
    decode_gathers = [
        s for s in sites
        if s["path"] == "mxnet_tpu/serving/decode/sharding.py"
        and s["kind"] == "all_gather" and s["sanction"] == "gather-ok"
        and "ShardedDecodeModel" in (s.get("region") or "")]
    assert decode_gathers == []
    # ...replaced by the four allclose-sanctioned psum sites (assembly /
    # Megatron block / 2bit wire / tied unembed)
    decode_psums = [
        s for s in sites
        if s["path"] == "mxnet_tpu/serving/decode/sharding.py"
        and s["kind"] == "psum" and s["sanction"] == "allclose-ok"]
    assert len(decode_psums) == 4


def test_decode_region_holds_the_megatron_psum_budget():
    # the compute-parallel rewrite: the decode region's budget covers
    # exactly its four static psum sites (assembly, Megatron block, 2bit
    # wire, tied unembed) and not one gather
    _sites, budgets = sharding_lint.collective_map_entries(REPO)
    decode = [b for b in budgets
              if b["region"] == "ShardedDecodeModel._build_fn.body"]
    assert len(decode) == 1
    assert decode[0]["budget"] == {"psum": 4}
    assert decode[0]["counts"].get("psum", 0) == 4
    assert decode[0]["counts"].get("all_gather", 0) == 0


def test_collective_map_is_fresh_and_justified():
    entries = sharding_lint.collective_map_entries(REPO)
    sites, _budgets = entries
    assert sites, "the runtime has sanctioned collective sites"
    assert all(s["reason"].strip() for s in sites)
    with open(COLLECTIVE_MAP) as f:
        committed = f.read()
    assert committed == sharding_lint.render_collective_map(entries), \
        ("docs/COLLECTIVE_MAP.md is stale: run "
         "`python tools/mxlint.py --collective-map`")


# ---------------------------------------------------------------------------
# the twin contract: static site counts == runtime counter deltas
# ---------------------------------------------------------------------------

def test_sharding_fixture_caught_statically_and_dynamically():
    from mxnet_tpu.parallel.collectives import (collective_totals,
                                                reset_collective_counters)
    src = _fixture("bad_sharding.py")
    static = sharding_lint.site_counts(
        sharding_lint.source_collective_sites(src, "bad_sharding.py"))
    mod = _load_fixture_module("bad_sharding.py")
    assert static == mod.GROUND_TRUTH
    reset_collective_counters()
    try:
        mod.drive()
        dynamic = {k: v["calls"] for k, v in collective_totals().items()}
    finally:
        reset_collective_counters()
    assert dynamic == mod.GROUND_TRUTH


def test_counter_snapshot_and_reset_api():
    from mxnet_tpu.parallel.collectives import (collective_counters,
                                                collective_totals,
                                                reset_collective_counters)
    mod = _load_fixture_module("clean_sharding.py")
    reset_collective_counters()
    try:
        mod.drive()
        per_axis = collective_counters()
        assert per_axis["all_gather"]["tp"]["calls"] == 1
        assert per_axis["all_gather"]["tp"]["bytes"] > 0
        assert per_axis["psum"]["tp"]["calls"] == 1
        # totals aggregate over axes and a passed snapshot is honoured
        totals = collective_totals(per_axis)
        assert totals["all_gather"]["calls"] == 1
        # the snapshot is a copy: later resets must not mutate it
        reset_collective_counters()
        assert collective_counters() == {}
        assert per_axis["all_gather"]["tp"]["calls"] == 1
    finally:
        reset_collective_counters()


def test_profiler_counters_gate_on_active_session():
    from mxnet_tpu import profiler
    from mxnet_tpu.parallel import collectives
    mod = _load_fixture_module("clean_sharding.py")
    collectives.reset_collective_counters()
    try:
        mod.drive()
        # no profiling session: the per-call profiler Counter writers
        # must not run (Counter.set_value appends trace events
        # unconditionally — an unbounded buffer in a long-lived server)
        assert collectives._PROF_COUNTERS == {}
        profiler.set_state("run")
        mod.drive()
        key = ("all_gather", "tp")
        assert key in collectives._PROF_COUNTERS
        counter = collectives._PROF_COUNTERS[key]
        assert counter._value == collectives.collective_counters()[
            "all_gather"]["tp"]["calls"]
    finally:
        profiler.set_state("stop")
        collectives.reset_collective_counters()


def test_axis_size_is_exempt_from_counting():
    # axis_size is a trace-time constant (psum of literal 1) — the lint
    # skips it and the runtime twin must not count it either
    from mxnet_tpu.parallel.collectives import (collective_totals,
                                                reset_collective_counters)
    src = """\
    from jax.sharding import Mesh
    from mxnet_tpu.parallel.collectives import axis_size

    def make_mesh(devs):
        return Mesh(devs, ("tp",))

    def run():
        return axis_size("tp")
    """
    assert _pairs(_analyze(src)) == []
    import jax
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from mxnet_tpu.parallel.collectives import axis_size
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    reset_collective_counters()
    try:
        out = shard_map(lambda: axis_size("tp"), mesh=mesh, in_specs=(),
                        out_specs=P(), check_vma=False)()
        assert int(np.asarray(out)) == 2
        assert collective_totals() == {}
    finally:
        reset_collective_counters()


# ---------------------------------------------------------------------------
# the decode-step acceptance cross-check (static model == wire truth)
# ---------------------------------------------------------------------------

def test_decode_step_static_prediction_matches_runtime():
    import jax.numpy as jnp
    from mxnet_tpu.parallel.collectives import (collective_totals,
                                                reset_collective_counters)
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
    reset_collective_counters()
    try:
        model.decode_fn(p, jnp.zeros((S,), jnp.int32),
                        jnp.zeros((S,), jnp.int32),
                        jnp.zeros((S, W), jnp.int32),
                        k_pool._data, v_pool._data)
        measured = collective_totals()
    finally:
        reset_collective_counters()
    predicted = sharding_lint.predict_decode_step_collectives(
        model, slots=S)
    psums = measured["psum"]
    # exact agreement, calls AND bytes — the abstract sharding model is
    # the wire truth, not an estimate
    assert psums["calls"] == predicted["psum"]["calls"]
    assert psums["calls"] == 2 * model.num_layers + 2
    assert psums["bytes"] == predicted["psum"]["bytes"]
    # the compute-parallel kernels pay ZERO gathers: weights contract
    # locally, the K/V pools never leave their head shard (the deleted
    # gather tax; statically the region holds budget(psum=4))
    assert measured.get("all_gather", {"calls": 0})["calls"] == 0
    assert predicted["all_gather"] == {"calls": 0, "bytes": 0}


# ---------------------------------------------------------------------------
# SPD004 fixes are real: eager extent-naming ValueErrors
# ---------------------------------------------------------------------------

def test_ulysses_rejects_indivisible_sequence_eagerly():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import ulysses_parallel_attention
    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
    q = jnp.zeros((1, 2, 7, 4), jnp.float32)   # T=7 % sp=2 != 0
    with pytest.raises(ValueError, match=r"sequence length of 7.*extent 2"):
        ulysses_parallel_attention(mesh, q, q, q)


def test_ring_attention_rejects_indivisible_sequence_eagerly():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import sequence_parallel_attention
    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
    q = jnp.zeros((1, 2, 5, 4), jnp.float32)   # T=5 % sp=2 != 0
    with pytest.raises(ValueError, match=r"sequence length of 5.*extent 2"):
        sequence_parallel_attention(mesh, q, q, q)


def test_moe_rejects_indivisible_extents_eagerly():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import make_expert_parallel_moe
    mesh = Mesh(np.array(jax.devices()[:2]), ("ep",))
    moe = make_expert_parallel_moe(mesh, lambda p, t: t, k=1)
    gate = jnp.zeros((4, 3), jnp.float32)
    with pytest.raises(ValueError, match=r"expert count of 3.*extent 2"):
        moe({"w": jnp.zeros((3, 4, 4))}, gate, jnp.zeros((4, 4)))
    gate2 = jnp.zeros((4, 4), jnp.float32)
    with pytest.raises(ValueError, match=r"token batch of 5.*extent 2"):
        moe({"w": jnp.zeros((4, 4, 4))}, gate2, jnp.zeros((5, 4)))


# ---------------------------------------------------------------------------
# registration: registry, CLI, --since auto-include, bench schema
# ---------------------------------------------------------------------------

def test_spd_pass_is_registered():
    assert "spd" in common.PASS_REGISTRY
    assert common.RULE_FAMILY_PASS["SPD"] == "spd"
    runner = common.resolve_runner("spd")
    assert runner is sharding_lint.run
    assert common.pass_of_key("SPD001|a.py|f|d") == "spd"


def test_cli_spd_pass_clean():
    proc = _run_mxlint("--passes", "spd")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


def test_since_mode_auto_includes_spd(tmp_path):
    pkg = tmp_path / "mxnet_tpu"
    par = pkg / "parallel"
    par.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (par / "__init__.py").write_text("")
    (par / "mesh0.py").write_text(
        'from jax.sharding import Mesh\n'
        'def make(devs):\n'
        '    return Mesh(devs, ("tp",))\n')
    root = str(tmp_path)
    subprocess.run(["git", "init", "-q"], cwd=root, check=True)
    subprocess.run(["git", "add", "-A"], cwd=root, check=True)
    subprocess.run(["git", "-c", "user.name=t", "-c", "user.email=t@t",
                    "commit", "-qm", "seed"], cwd=root, check=True)

    # nothing under parallel/ changed: the spd pass is skipped entirely
    proc = _run_mxlint("--root", root, "--since", "HEAD",
                       "--passes", "spd", "--no-baseline", "--json")
    assert proc.returncode == 0, proc.stderr
    assert json.loads(proc.stdout)["findings"] == []

    # an untracked parallel/ file with an un-sanctioned collective: the
    # pass runs, and its findings bypass the changed-file filter (the
    # unused-axis finding lands in mesh0.py, which did NOT change)
    (par / "new_kernel.py").write_text(
        'from mxnet_tpu.parallel.collectives import allreduce\n'
        'def step(x):\n'
        '    return allreduce(x, "tp")\n')
    proc = _run_mxlint("--root", root, "--since", "HEAD",
                       "--passes", "spd", "--no-baseline", "--json")
    assert proc.returncode == 1, proc.stderr
    found = json.loads(proc.stdout)["findings"]
    rules = sorted(f["rule"] for f in found)
    assert "SPD002" in rules
    assert [f["path"] for f in found if f["rule"] == "SPD002"] \
        == ["mxnet_tpu/parallel/new_kernel.py"]


def test_ci_lint_runs_spd():
    with open(os.path.join(REPO, "tools", "ci_lint.sh")) as f:
        script = f.read()
    assert "spd" in script or "mxlint.py\n" in script or \
        "--passes" not in script, \
        "ci_lint.sh must run the spd pass (default pass list covers it)"
