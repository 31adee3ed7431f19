"""Model step: model operations of the tokens served in the window
(``bench/roofline/step.py``) over the summed step wall time times the
chip's bf16 peak, in %."""


def read(w):
    if not w.steps or not w.step_work:
        return None
    step = w.roofline("step")
    total = sum(step.flops([(f, ctx, got) for _, ctx, f, got in work],
                           w.dims) for work in w.step_work)
    wall = sum(b - a for a, b in w.steps)
    if total <= 0 or wall <= 0:
        return None
    return 100.0 * total / (wall * w.peaks.flops_bf16)
