"""Model step, decode: the FLOPs the window's untraced decode steps require
(from shapes and live context lengths, the architecture module's
``decode_step_flops``) over their summed host time times the chip's bf16
peak, in percent.  The host time of a step covers the whole ``Engine.step``
call: page tables, the decode program, the logits' copy to the host and
sampling."""


def read(run):
    steps = [i for i in run.iters if i.kind == "step"]
    secs = sum(i.end - i.start for i in steps)
    if not steps or secs <= 0:
        return None
    flops = sum(run.arch.decode_step_flops(run.m, i.ctx_lens) for i in steps)
    return 100.0 * flops / (secs * run.peak.bf16_flops)
