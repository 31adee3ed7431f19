"""Scheduler admission and queue: the mean host-clock wait of a turn in
the engine, from where it entered (a new session's submit, a tool's
result) or was put back in the queue, to where the step that first
computes for it issued its model work; from the engine's
``host_queue_wait_s`` over ``host_queue_waits``, in ms."""


def read(w):
    waits = w.delta.get("host_queue_waits")
    secs = w.delta.get("host_queue_wait_s")
    if not waits or secs is None:
        return None
    return 1000.0 * secs / waits
