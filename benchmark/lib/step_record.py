"""Arithmetic over the program's step record, as `kinds/serve._monitor_delta`
hands it to the readers, over the window:

    serving/step_time{phase=<kind>}:sum|count   readback to readback, a
                                                program step of that kind
    serving/step_wait{phase=<kind>}:sum|count   of it, blocked in the
                                                readback: the device's lead
    serving/host_cpu{phase=<name>}              the thread's own CPU seconds
                                                inside a host phase
    serving/host_stalls, serving/host_stall_seconds
    serving/steps_dispatched{in_flight=0|1}

With a step in flight a step costs the longer of the device's side and the
host's; `step_time` is that maximum, `step_time - step_wait` the host's side
whole (its phases and what lies between them).  PERF.md section 3 lists the
series; `paddle_tpu/serving/engine.py` observes them.

A program without the record (a commit before it) gives every function here
None, and the reader leaves its metric out of the line.
"""
from benchmark.lib.host_phases import phase_seconds

# the host's phases that should never wait: `engine/readback` waits for the
# device and `api/drain_submits` blocks on its queue, both by design
BUSY_PHASES = ("engine/schedule", "engine/prepare", "engine/sample_dispatch",
               "engine/emit", "engine/retire", "api/push_progress")


def _step(counters, series, kind, part):
    return counters.get(f"serving/{series}{{phase={kind}}}:{part}", 0)


def has_record(counters):
    """Does the program keep a step record at all?"""
    return any(k.startswith("serving/step_wait{") for k in counters)


def step_split(counters, kind="decode"):
    """(seconds of `kind` steps, of them waited, steps) over the window, or
    None without the record or without such a step."""
    steps = _step(counters, "step_wait", kind, "count")
    if not steps:
        return None
    return (_step(counters, "step_time", kind, "sum"),
            _step(counters, "step_wait", kind, "sum"), steps)


def host_ms(counters, kind="decode"):
    """Milliseconds the host needs between two readbacks of a step."""
    split = step_split(counters, kind)
    if split is None:
        return None
    total, waited, steps = split
    return 1e3 * (total - waited) / steps


def wait_share(counters, kind="decode"):
    """Percent of a step the host waits for the device."""
    split = step_split(counters, kind)
    if split is None or not split[0]:
        return None
    return 100.0 * split[1] / split[0]


def cpu_share(counters, phases=BUSY_PHASES):
    """Percent of the named phases' wall time the thread ran."""
    keys = [f"serving/host_cpu{{phase={p}}}" for p in phases]
    wall = phase_seconds(counters, phases)
    if not wall or not any(k in counters for k in keys):
        return None
    return 100.0 * sum(counters.get(k, 0.0) for k in keys) / wall


def pipeline_full_share(counters):
    """Percent of program steps dispatched behind a step still owed."""
    empty, full = (counters.get(f"serving/steps_dispatched{{in_flight={i}}}")
                   for i in (0, 1))
    if not (empty or full):
        return None
    return 100.0 * (full or 0) / ((empty or 0) + (full or 0))


def stall_seconds(counters):
    """Seconds of host side in steps that stalled: 0 in a sound run."""
    if not has_record(counters):
        return None
    return counters.get("serving/host_stall_seconds", 0.0)
