"""Whole-step share of the chip's peak in the eval window, %: the model
operations of every step in the window (from the equations and each
batch's valid events and neighbors, roofline/steps.py), over the window's
seconds, over the peak FLOP/s of peaks.json."""


def read(run):
    if run.loop != "eval" or run.peak is None or not run.work:
        return None
    ops = sum(model_ops for _, model_ops in run.work)
    return 100.0 * ops / run.records["window_s"] / run.peak["flops_per_s"]
