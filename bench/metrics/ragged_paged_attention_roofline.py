"""Kernel: ``ragged_paged_attention``'s share of its roofline, in %: the
least time the chip could take for the calls of the window (per call the
larger of operations over peak FLOP/s and bytes over peak bandwidth,
``bench/roofline/ragged_paged_attention.py``; one call per layer per step)
over the kernel's summed device time in the trace."""

KERNEL = "ragged_paged_attention"


def read(w):
    if w.trace is None or not w.step_work:
        return None
    device_s = w.trace.kernel_s.get(KERNEL, 0.0)
    if device_s <= 0:
        return None
    fn = w.roofline(KERNEL)
    least = 0.0
    for work in w.step_work:
        fl, by = fn.cost([(n, ctx) for n, ctx, _, _ in work if n > 0],
                         w.dims)
        least += w.dims["n_layers"] * max(fl / w.peaks.flops_bf16,
                                          by / w.peaks.hbm_bytes_per_s)
    return 100.0 * least / device_s
