"""How often the attention forward kernel runs in a step: the operations
named ``attention_fwd`` (the name the program gives that ``pallas_call``)
inside each run of the step program, the median over the runs.

One call a decoder layer is required.  A layer that is recomputed in the
backward pass runs the kernel a second time unless it keeps the kernel's two
results (``hybridize(remat_policy=("attn.out", "attn.lse"))``), so twice the
layers says the recomputed region still holds the kernel.  The runs are those
``attn_kernel_roofline.train`` bounds its kernel time by
(``Device.steps()``); the median, because the trace starts inside a run, whose
first kernels it then lacks (my chip runs, PR 30: 3 of the first run's calls,
so the mean read 11.8 and 5.8).  Silent, never 0, where the trace shows
none."""
import bisect
import statistics

KERNEL = "attention_fwd"


def calls_per_step(device, name):
    starts = sorted(s for op, s, _ in device.ops if op.startswith(name))
    calls = [bisect.bisect_left(starts, hi) - bisect.bisect_left(starts, lo)
             for lo, hi in device.steps()]
    return statistics.median(calls) if any(calls) else None


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    return calls_per_step(trace.devices[0], KERNEL)
