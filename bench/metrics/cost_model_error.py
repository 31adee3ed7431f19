"""Scheduler cost model: how far the step times the scheduler prices
(``engine_cost_step_s``, the virtual seconds ``CostModel`` charges each
iteration) are from the host-clock step times (``host_step_s``), summed
over the window: 100 x |measured - priced| / measured, in %."""


def read(w):
    measured = w.delta.get("host_step_s")
    priced = w.delta.get("engine_cost_step_s")
    if not measured or priced is None:
        return None
    return 100.0 * abs(measured - priced) / measured
