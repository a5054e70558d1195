"""Share of its roofline that the ``ragged_expert_matmul`` kernel reaches
in the window's prefill programs.

For each prefill execution in the trace (in order, matched one to one with
the harness's record of prefill calls: prompt length, real rows, k), the
work the algorithm needs is every layer's three grouped expert matmuls
over the real rows' (token, expert) assignments, L x rows x k, reading the
weights and adapters of all E experts: with at least 512 tokens per row,
an expert escapes every one of L x k assignments with probability under
(1 - 1/E)^512 < 0.0004.  Bucket-padding rows and segment padding are not
counted.  Each call's least time is the larger of its FLOPs over the peak
rate and its bytes over the peak bandwidth; the share is the sum of those
over the sum of the kernel's device time.  Decode calls are not read:
their needed bytes depend on which experts the step's rows touched, which
the engine does not report.
"""
from harness import flops, trace as tr
from harness.chip import log

KERNEL = "ragged_expert_matmul"
MODULE = "jit__prefill_fn"


def read(ctx):
    t, s, pk = ctx.trace, ctx.sizes, ctx.peaks
    chip = min(t.modules)
    mods = [m for m in t.modules[chip] if m[0] == MODULE
            and ctx.trace_lo <= m[1] < ctx.trace_hi]
    calls = ctx.data["prefills"]
    if not mods or len(mods) != len(calls):
        log(f"{KERNEL}_roofline: {len(mods)} prefill executions traced, "
            f"{len(calls)} recorded; not read")
        return None
    kern = [e for e in t.ops[chip] if tr.is_kernel(e[0], KERNEL)]
    need_s = got_s = 0.0
    n_mem = n_all = 0
    for (length, rows, k), evs in zip(calls, tr.within(kern, mods)):
        per_layer = flops.moe_layer_matmuls(s, length * rows * k, s.n_experts)
        if not evs:
            continue
        for fl, by in per_layer * s.layers:
            tf, tb = fl / pk["bf16_flops_per_s"], by / pk["hbm_bytes_per_s"]
            need_s += max(tf, tb)
            n_mem += tb > tf
            n_all += 1
        got_s += sum(e - b for _, b, e in evs) / 1e9
    if got_s <= 0:
        return None
    log(f"{KERNEL}_roofline: {n_all} calls, {n_mem} bound by bytes, the "
        f"rest by FLOPs; needed {need_s} s, kernel time {got_s} s")
    return 100.0 * need_s / got_s
