"""Kernel ``kernels/flash_attention.py``: share of its roofline over the traced
prefills, in percent.  The least time is, per call (one layer of one
prefill), the larger of its FLOPs over peak and its bytes over bandwidth,
counted over the real prompt tokens with the causal half of the scores (the
architecture module's cost of the kernel, ``KERNEL_COSTS``); it is divided
by the summed device time of the kernel's events in the trace, named
``flash_attention``."""

from trace_reduce import kernel_events, roofline_share

NAME = "flash_attention"


def read(run):
    if run.trace is None:
        return None
    events = kernel_events(run.trace.device, (NAME,))
    cost = run.arch.KERNEL_COSTS[NAME]
    layers = run.m["num_layers"]
    calls = [cost(run.m, i.ctx_lens[0])
             for i in run.trace.iters if i.kind == "admit"] * layers
    share = roofline_share(calls, sum(e.dur for e in events),
                           run.peak.bf16_flops, run.peak.hbm_bytes_per_s)
    return None if share is None or not events else share[0]
