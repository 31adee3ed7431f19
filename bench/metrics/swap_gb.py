"""KV swap traffic over the window, out and in, from the engine's swap
byte counter, in GB (1e9 bytes)."""


def read(w):
    if "engine_swap_bytes" not in w.delta:
        return None
    return w.delta["engine_swap_bytes"] / 1e9
