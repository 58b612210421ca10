"""Share of its roofline reached by the flat scoring kernel (``l2_dist``).

The least time of the window's flat scoring work (``bench/work.py``) at
the chip's peaks, over the device time of the kernel's ops in the trace.
"""

from bench import work

EVENTS = ("_l2_dist_kernel", "l2_dist")


def read(run):
    if run.trace is None or run.pq_m:
        return None
    t = run.trace.kernel_s(EVENTS)
    if t <= 0:
        return None
    need, bound = work.least_seconds(
        work.scoring_work(run.flush_probes(), run.list_sizes, run.d),
        run.peak())
    return 100.0 * need / t, f"bound by {bound}, kernel {t:.6f} s"
