"""Writes ``recorded_scopes.json.gz``, the hand-made pair that
``test_scopes.py`` reads: a ``Summary`` of four runs of a step program and the
optimized HLO text, in the form ``profiler._hlo_text`` prints it, that the
program's table of instructions is parsed from.

    python3 -m benchmark.checks.recorded_scopes
"""
import gzip
import json
import os

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))
RUN_NS, GAP_NS = 1_000_000, 1_000
BWD = "bwd/transpose(jvp(fwd))/jvp()/checkpoint/"
# (instruction, the rest of its compact name, ns, op_name or None, computation)
OPS = [
    ("fusion.1", "fusion kLoop f32[64,2048]", 50_000,
     "fwd/jvp(lm.embed)/jit(Embedding)/jit(_take)/gather", "entry"),
    ("attention_fwd.2", "custom-call f32[32,64,128]", 100_000,
     "fwd/jvp(attn.block_mask)/attention_fwd", "entry"),
    ("fusion.3", "fusion kOutput f32[64,4096]", 60_000,
     "fwd/jvp(attn.proj)/jit(FullyConnected)/dot_general", "entry"),
    ("fusion.4", "fusion kLoop f32[64,128]", 10_000,
     "fwd/jvp(moe.route)/exp", "entry"),
    ("sort.5", "sort s32[512]", 20_000, "fwd/jvp(moe.sort)/sort", "entry"),
    ("moe_experts_hidden.6", "custom-call bf16[512,768]", 80_000,
     "fwd/jvp(moe.experts)/moe_experts_hidden", "entry"),
    ("fusion.7", "fusion kLoop f32[64,2048]", 30_000,
     "fwd/jvp(moe.combine)/gather", "entry"),
    ("fusion.8", "fusion kLoop f32[64,2048]", 40_000,
     "fwd/jvp(jit(_contrib_rms_norm))/mul", "entry"),
    ("fusion.9", "fusion kInput f32[]", 10_000,
     "fwd/jvp(loss)/jit(pick)/reduce_sum", "entry"),
    ("while.10", "while s32[]", 200_000, BWD + "while", "entry"),
    ("fusion.11", "fusion kOutput f32[512,768]", 70_000,
     BWD + "rematted_computation/moe.experts/dot_general", "body"),
    ("fusion.12", "fusion kOutput f32[64,4096]", 50_000,
     BWD + "rematted_computation/attn.proj/jit(FullyConnected)/dot_general",
     "body"),
    ("fusion.13", "fusion kLoop bf16[512,2048]", 30_000,
     BWD + "moe.sort/gather", "body"),
    ("fusion.14", "fusion kLoop f32[64,2048]", 40_000,
     BWD + "moe.combine/gather", "body"),
    ("copy-done.21", "copy-done f32[64,2048]", 10_000, None, "entry"),
    ("moe_experts_bwd.15", "custom-call bf16[512,768]", 120_000,
     BWD + "moe.experts/moe_experts_bwd", "entry"),
    ("attention_bwd.16", "custom-call f32[32,64,128]", 110_000,
     BWD + "attn.block_mask/attention_bwd", "entry"),
    ("fusion.17", "fusion kLoop f32[64,2048]", 60_000,
     "bwd/transpose(jvp(jit(_contrib_rms_norm)))/mul", "entry"),
    ("fusion.18", "fusion kLoop f32[2048,2048]", 45_000,
     "opt/jit(adam_update)/sub", "entry"),
    ("fusion.19", "fusion kLoop f32[]", 5_000, "metric/eq", "entry"),
    ("copy.20", "copy f32[64,2048]", 5_000, None, "entry"),
]
CUT = 3     # the trace starts inside the first run: it lacks its first three


def hlo():
    def line(name, rest, op_name, root, operand):
        opcode = rest.split(" ")[0]
        attrs = ""
        if opcode == "fusion":
            attrs = ", kind=%s, calls=fused_computation.%s" % (
                rest.split(" ")[1], name.split(".")[1])
        elif opcode == "while":
            attrs = ", condition=cond.1, body=body.1"
        if op_name:
            attrs += ', metadata={op_name="jit(train_step)/%s" ' \
                'stack_frame_id=7}' % op_name
        return "  %s%s = %s(%s)%s" % ("ROOT " if root else "", name, opcode,
                                      operand, attrs)

    def computation(head, parameter, where):
        rows = [o for o in OPS if o[4] == where]
        return [head + " {", "  %s = parameter(0)" % parameter] + [
            line(name, rest, op_name, i == len(rows) - 1, parameter)
            for i, (name, rest, _, op_name, _) in enumerate(rows)] + ["}", ""]

    out = ["HloModule jit_train_step, is_scheduled=true, "
           "entry_computation_layout={(f32[64]{0})->f32[64]{0}}", ""]
    for name, rest, _, op_name, _ in OPS:
        if rest.startswith("fusion"):
            n = name.split(".")[1]
            out += ["fused_computation.%s {" % n,
                    "  param_0.%s = parameter(0)" % n,
                    "  ROOT multiply.%s = multiply(param_0.%s, param_0.%s), "
                    'metadata={op_name="jit(train_step)/%s"}'
                    % (n, n, n, op_name), "}", ""]
    out += ["region_0.1 {", "  Arg_0.1 = parameter(0)",
            "  Arg_1.1 = parameter(1)",
            "  ROOT add.1 = add(Arg_0.1, Arg_1.1), metadata={op_name="
            '"jit(train_step)/fwd/jvp(loss)/reduce_sum"}', "}", "",
            "cond.1 {", "  p.1 = parameter(0)",
            "  ROOT compare.1 = compare(p.1, p.1), direction=LT", "}", ""]
    return "\n".join(out + computation("body.1", "p.2", "body")
                     + computation("ENTRY main.30", "p.0", "entry"))


def summary():
    ops, modules = [], []
    for run in range(4):
        start = run * (RUN_NS + GAP_NS)
        modules.append(("jit_train_step(1)", start, RUN_NS))
        # a small program between two steps whose operation shares a step
        # instruction's name
        modules.append(("jit_split(2)", start + RUN_NS + 100, 500))
        ops.append(("fusion.1 fusion kLoop u32[2]", start + RUN_NS + 100, 500))
        at, inside = start + 1_000, None
        for i, (name, rest, ns, _, where) in enumerate(OPS):
            listed = not (run == 0 and i < CUT)
            if where == "body":             # inside the container's time
                if listed:
                    ops.append((name + " " + rest, inside, ns))
                inside += ns
                continue
            if inside is not None and name != "while.10":
                at, inside = max(at, inside) + 10_000, None
            if listed:
                ops.append((name + " " + rest, at, ns))
            if name == "while.10":
                inside = at
            at += ns
        assert at <= start + RUN_NS
    device = trace.Device("/device:TPU:0", sorted(ops, key=lambda e: e[1]),
                          modules)
    return trace.Summary(0, 4 * (RUN_NS + GAP_NS), [device], [])


if __name__ == "__main__":
    with gzip.open(os.path.join(HERE, "recorded_scopes.json.gz"), "wt") as f:
        json.dump({"summary": summary().to_json(), "hlo": hlo()}, f)
