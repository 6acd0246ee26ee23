"""Set-up: seconds in backend compiles (persistent-cache loads
included) that end before the traced window opens, from the program's
compile log (``repro.obs.profile``). The window opens at its first
``bench.dispatch`` span, taken to the log's clock through the profile's
start stamp. A program without the log reads nothing."""
from bench import scopes


def window_open(run):
    """The window's opening on the compile log's ``perf_counter``
    clock; None where the trace has no start stamp."""
    from repro.obs import profile
    start_ns = scopes.phases(run).meta.start_ns
    if start_ns is None:
        return None
    return profile.wall_to_perf((start_ns + run.lo) * 1e-9)


def read(run):
    try:
        from repro.obs import profile
    except ImportError:
        return None
    log = profile.entries()
    t_open = window_open(run) if log else None
    if t_open is None:
        return None
    done = [e for e in log if e["end"] <= t_open]
    return profile.backend_seconds(done) if done else None
