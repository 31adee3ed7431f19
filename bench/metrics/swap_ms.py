"""KV swap staging: the host's time in the swap spans
(``engine.swap_out``: ``SwapStager.pack``; ``engine.swap_in``: the host
stack and ``unpack``; ``engine.swap_collect``: ``collect``, blocking on
the transfer, and the per-page slicing), from ``host_swap_s``, per step
whose plan was not empty (``host_steps``), in ms."""


def read(w):
    steps = w.delta.get("host_steps")
    secs = w.delta.get("host_swap_s")
    if not steps or secs is None:
        return None
    return 1000.0 * secs / steps
