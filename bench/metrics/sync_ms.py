"""Model step as the host waits on it: the host's time in ``engine.sync``,
the fetch of the fused step's sampled ids, blocked until the device has
run the step, from ``host_sync_s``, per step whose plan was not empty
(``host_steps``), in ms."""


def read(w):
    steps = w.delta.get("host_steps")
    secs = w.delta.get("host_sync_s")
    if not steps or secs is None:
        return None
    return 1000.0 * secs / steps
