"""Engine step: the share of the token rows the model dispatches computed
that only pad the power-of-two bucket, from the engine's ``pad_tokens``
over its ``step_tokens`` (every dispatch, fused or not), in %."""


def read(w):
    total = w.delta.get("engine_step_tokens")
    pad = w.delta.get("engine_pad_tokens")
    if not total or pad is None:
        return None
    return 100.0 * pad / total
