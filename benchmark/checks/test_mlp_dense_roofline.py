"""``mlp_dense_roofline.train`` (PR 38) on a hand-made table: three runs of
``jit_train_step`` of five operations each, two chosen by their table rows to
be the scope ``mlp.dense`` (a forward product, 2 ms; a weight gradient with
Adam's update fused in, 6 ms) and one its recomputed forward (1 ms), beside
an ``attn.proj`` operation (3 ms) and the optimizer's own (1 ms): the scope
is 9 ms a step, the required work that of the fifth cell's one dense layer
over 16,384 rows.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/checks -q
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness, scopes, trace  # noqa: E402

METRIC = "mlp_dense_roofline.train"
PEAK = 197e12
TABLE = {"fusion.1": ("fwd", "mlp.dense", False, "fusion"),
         "fusion.2": ("bwd", "mlp.dense", False, "fusion"),
         "fusion.3": ("bwd", "mlp.dense", True, "fusion"),
         "fusion.4": ("bwd", "attn.proj", False, "fusion"),
         "fusion.5": ("opt", None, False, "fusion")}
MS = {"fusion.1": 2, "fusion.2": 6, "fusion.3": 1, "fusion.4": 3,
      "fusion.5": 1}


def summary():
    ops, modules, t = [], [], 0
    for _ in range(3):
        modules.append(("jit_train_step", t, 13_000_000))
        for name, ms in MS.items():
            ops.append(("%s fusion kOutput f32[8]" % name, t, ms * 1_000_000))
            t += ms * 1_000_000
        t += 50_000
    return trace.Summary(0, t, [trace.Device("TPU:0", ops, modules)], [])


def run_of(cell, table=TABLE):
    return {"cell": harness.Cell(cell, ROOT), "trace": summary(),
            "peaks": {"flops_per_s": PEAK}, "table": table}


@pytest.fixture(autouse=True)
def the_table(monkeypatch):
    """The program's table, as ``profiler.program_ops`` would give it."""
    current = {}
    monkeypatch.setattr(scopes, "program_table", lambda: (
        (current["table"], 0.01, {}) if current.get("table") else None))
    return current


def read(run, the_table):
    the_table["table"] = run.pop("table")
    return harness.load_reader(METRIC)(run)


def test_the_fifth_cell_s_dense_layer_over_its_scope(the_table):
    # 1 dense layer held x 16,384 rows x 3 products x 2048 x 11776 x 2 x 3
    need = 16384 * 3 * 2048 * 11776 * 2 * 3
    assert need == pytest.approx(7.107e12, rel=1e-3)
    got = read(run_of("lfm2_24b_a2b_ep8.sft_b2_s8192"), the_table)
    assert got == pytest.approx(100 * need / PEAK / 0.009)


@pytest.mark.parametrize("why", ["no dense layer held", "no table",
                                 "no such scope", "no trace"])
def test_silent_with(the_table, why):
    cell = "lfm2_24b_a2b_ep8.sft_b2_s8192"
    table = TABLE
    if why == "no dense layer held":        # every layer routed
        cell = "sdar_30b_a3b_ep8.bd_b1_s4096"
    elif why == "no table":
        table = None
    elif why == "no such scope":
        table = {k: (p, "conv.proj" if s == "mlp.dense" else s, r, o)
                 for k, (p, s, r, o) in TABLE.items()}
    run = run_of(cell, table)
    if why == "no trace":
        run["trace"] = None
    assert read(run, the_table) is None


def test_the_dense_layers_held_are_counted_by_their_published_index():
    reader = harness.load_reader(METRIC).__globals__
    config = harness.Cell("lfm2_24b_a2b_ep8.sft_b2_s8192", ROOT).config
    assert reader["dense_layers"](config) == 1
    assert reader["dense_layers"](dict(config, deployment={
        "layers": [0, 1, 2]})) == 2
    assert reader["required_flops"](config, {"seq_len": 8192}) \
        == 8192 * 3 * 2048 * 11776 * 6
