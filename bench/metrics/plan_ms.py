"""Scheduler plan: the host's self time in the engine's ``engine.plan``
span (admission, resumes, faults, prefix-cache match,
``Scheduler.next_iteration``, page alignment), from the engine's
``host_plan_s`` counter, per step whose plan was not empty
(``host_steps``), in ms."""


def read(w):
    steps = w.delta.get("host_steps")
    secs = w.delta.get("host_plan_s")
    if not steps or secs is None:
        return None
    return 1000.0 * secs / steps
