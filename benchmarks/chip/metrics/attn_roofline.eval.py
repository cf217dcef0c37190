"""Fused temporal-attention kernel's share of its roofline in the eval
window, %: the least time the chip needs for the work of every forward
kernel call in the window (the larger of operations over peak FLOP/s and
compulsory bytes over peak bytes/s, per call: roofline/attention.py),
over the device time of the kernel's events in the trace."""

from chip.kernels import is_attention_kernel


def read(run):
    if run.loop != "eval" or run.peak is None or not run.work:
        return None
    kernel_s = run.reduced.seconds_of(is_attention_kernel)
    if kernel_s <= 0:
        return None
    least = sum(w.seconds(run.peak) for calls, _ in run.work for w in calls)
    return 100.0 * least / kernel_s
