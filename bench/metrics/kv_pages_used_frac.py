"""KV pool: share of the pool's pages in use, ``1 - free_pages / num_pages``
after each decode step, averaged over the window's untraced decode steps."""


def read(run):
    used = [1.0 - i.free_pages / run.num_pages for i in run.iters if i.kind == "step"]
    return sum(used) / len(used) if used else None
