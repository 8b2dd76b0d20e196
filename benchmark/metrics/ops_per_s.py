"""History operations whose verdict came back in the window, over the
window's seconds (keyed runs count every key's operations)."""


def read(run):
    return sum(ck.item.ops for ck in run.window) / run.window_s
