"""The whole meta-step's share of the chips' bf16 peak, in percent: the
model FLOPs a step requires (``bench/flops.py``) times the steps of the
traced tail of the window, over its seconds on the host clock (the span
``bench.window``), the chips and the peak of ``bench/peaks.json``."""


def read(run):
    if run.peaks is None or run.trace is None:
        return None
    achieved = run.step_flops * run.traced_steps / run.trace.window_s
    return 100.0 * achieved / (run.chips * run.peaks["bf16_flops_per_s"])
