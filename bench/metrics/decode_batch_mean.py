"""Batching: live slots per decode step (``Engine.num_live`` as the step
starts), averaged over the window's untraced decode steps."""


def read(run):
    live = [len(i.ctx_lens) for i in run.iters if i.kind == "step"]
    return sum(live) / len(live) if live else None
