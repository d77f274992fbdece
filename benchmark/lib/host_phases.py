"""Arithmetic over the program's `serving/host_time{phase=<name>}`
histogram, as `kinds/serve._monitor_delta` hands it to the readers: one
`...:sum` (seconds) and one `...:count` per phase, over the window.

The program opens a phase (`paddle_tpu.monitor.trace.phase`) at every
boundary of the serving loop; PERF.md section 3 lists them.  While the
thread that drives the engine is in one of GAP_PHASES the device has
nothing queued; `engine/sample_dispatch` runs under the model program and
`engine/readback` waits for the device, so neither is host gap.

A program without the histogram (a commit before the phases) gives every
function here None, and the reader leaves its metric out of the line.
"""

GAP_PHASES = ("api/drain_submits", "api/push_progress", "engine/schedule",
              "engine/prepare", "engine/emit", "engine/retire")


def phase_seconds(counters, phases):
    """Summed seconds of the named phases over the window, or None."""
    keys = [f"serving/host_time{{phase={p}}}:sum" for p in phases]
    if not any(k in counters for k in keys):
        return None
    return sum(counters.get(k, 0.0) for k in keys)


def program_steps(counters):
    """Engine steps that ran a program: prefill steps and decode steps."""
    return sum(counters.get(f"serving/step_time{{phase={p}}}:count", 0)
               for p in ("prefill", "decode"))


def per_step_ms(counters, phases):
    """Milliseconds the named phases take per program step, or None."""
    seconds, steps = phase_seconds(counters, phases), program_steps(counters)
    if seconds is None or not steps:
        return None
    return 1e3 * seconds / steps


def mean_ms(counters, histogram):
    """Mean of a histogram of seconds over the window, in ms, or None."""
    n = counters.get(histogram + ":count", 0)
    if not n:
        return None
    return 1e3 * counters[histogram + ":sum"] / n
