"""Engine dispatch: the host's self time in ``engine.dispatch`` less its
swap spans (block tables, host-to-device copies, issuing the fused step),
from ``host_dispatch_s``, per step whose plan was not empty
(``host_steps``), in ms."""


def read(w):
    steps = w.delta.get("host_steps")
    secs = w.delta.get("host_dispatch_s")
    if not steps or secs is None:
        return None
    return 1000.0 * secs / steps
