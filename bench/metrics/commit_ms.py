"""Engine commit: the host's self time in ``engine.commit`` less the sync
and swap-collect spans (ledger, accruals, ``apply_plan``, session
boundaries, events), from ``host_commit_s``, per step whose plan was not
empty (``host_steps``), in ms."""


def read(w):
    steps = w.delta.get("host_steps")
    secs = w.delta.get("host_commit_s")
    if not steps or secs is None:
        return None
    return 1000.0 * secs / steps
