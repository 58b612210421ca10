"""Device time of the top-k select kernel (``seg_topk``) per query
answered in the window, in microseconds; nothing where the select ran on
the host."""

EVENTS = ("_seg_topk_kernel", "seg_topk")


def read(run):
    if run.trace is None or run.window.queries == 0:
        return None
    t = run.trace.kernel_s(EVENTS)
    if t <= 0:
        return None
    return 1e6 * t / run.window.queries
