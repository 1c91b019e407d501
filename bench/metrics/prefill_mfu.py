"""Model step, prefill: the FLOPs the window's untraced admissions require
(real prompt tokens, the causal half of attention, no padding; the
architecture module's ``prefill_flops``) over the summed host time of those
``Engine.admit`` calls times the chip's bf16 peak, in percent."""


def read(run):
    admits = [i for i in run.iters if i.kind == "admit"]
    secs = sum(i.end - i.start for i in admits)
    if not admits or secs <= 0:
        return None
    flops = sum(run.arch.prefill_flops(run.m, i.ctx_lens[0]) for i in admits)
    return 100.0 * flops / (secs * run.peak.bf16_flops)
