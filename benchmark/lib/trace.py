"""From a profiler trace (.xplane.pb) to busy time, idle share, the
operations that took most time and the longest idle gaps.

Two steps, so the second can be checked on a small recorded fixture:

  read_xplane(path) -> {"devices": {plane: [[name, start_ns, dur_ns], ..]},
                        "host": [[name, start_ns, dur_ns], ..]}
  summarize(events) -> the numbers

Only the `XLA Ops` line of each `/device:TPU:n` plane is reduced.  The
other lines of a device plane (`Steps`, `XLA Modules`, `Async XLA Ops`,
`TC Overlay`) cover the same time again, so summing a whole plane counts
it more than once.  Host events are kept only where their name starts
with `bench:` - the spans the benchmark's runners write with
`jax.profiler.TraceAnnotation`.
"""
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench:"


def read_xplane(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    return {"devices": devices, "host": host}


_LAYOUT = re.compile(r"\{[^{}]*\}")


_NUMBER = re.compile(r"\.\d+$")


def short_name(name, limit=120):
    """'%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(...)' ->
    'fusion bf16[8,128]': the HLO instruction's name without its number,
    and its result shape - so the 24 layers' copies of one operation, and
    its occurrences in every step, add up under one name."""
    head, sep, rest = name.partition(" = ")
    head = _NUMBER.sub("", head.lstrip("%"))
    if not sep:
        return head[:limit]
    rest = _LAYOUT.sub("", rest)
    if rest.startswith("("):                  # tuple result
        shape = rest[:rest.find(")") + 1]
    else:
        shape = rest.split(" ", 1)[0]
    return f"{head} {shape}"[:limit]


def is_pallas(name):
    """A Mosaic kernel: an HLO custom-call whose target is tpu_custom_call
    (XLA's own custom calls, such as ConcatBitcast, have other targets)."""
    return 'custom_call_target="tpu_custom_call"' in name


def is_optimizer_update(name):
    """An AdamW update fusion: it reads the step's `state_vals_*` buffers
    and returns exactly three arrays of one shape - the new weight and its
    two new moments."""
    _, sep, rest = name.partition(" = ")
    if not sep or "state_vals_" not in rest or not rest.startswith("("):
        return False
    rest = _LAYOUT.sub("", rest)
    shapes = [s.strip() for s in rest[1:rest.find(")")].split(", ")]
    return len(shapes) == 3 and len(set(shapes)) == 1


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _covering(host, t):
    """Name of the shortest bench: span that covers instant t, if any."""
    best = None
    for name, s, d in host:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0][len(HOST_PREFIX):] if best else None


def summarize(events, top=10):
    """Numbers of one traced slice.  The slice is the span from the first
    device operation's start to the last one's end, over all chips; a chip
    is busy where any `XLA Ops` event runs on it.

    Returns None when no operation ran on a device, else a dict:
      window_s, busy_s (mean over chips), busy_s_per_chip,
      idle_share (1 - busy_s/window_s),
      device_ops [[short name, seconds]] - most time first, summed over
        occurrences and chips,
      idle_gaps [[label, seconds]] - gaps of the first chip summed by
        label `<host span or host:unattributed> | <op before> -> <op
        after>`, most time first.
    """
    devices = {k: v for k, v in events["devices"].items() if v}
    if not devices:
        return None
    t0 = min(s for evs in devices.values() for _, s, _ in evs)
    t1 = max(s + d for evs in devices.values() for _, s, d in evs)
    window = (t1 - t0) / 1e9
    busy, by_op = {}, {}
    for plane, evs in devices.items():
        merged = _union([s, s + d] for _, s, d in evs)
        busy[plane] = sum(e - s for s, e in merged) / 1e9
        for name, _, d in evs:
            key = short_name(name)
            by_op[key] = by_op.get(key, 0.0) + d / 1e9
    first = sorted(devices)[0]
    evs = sorted(devices[first], key=lambda e: e[1])
    gaps, end, prev = {}, None, None
    for name, s, d in evs:
        if end is not None and s > end:
            host = _covering(events["host"], (s + end) // 2)
            label = (f"{host or 'host:unattributed'} | "
                     f"{short_name(prev, 40)} -> {short_name(name, 40)}")
            gaps[label] = gaps.get(label, 0.0) + (s - end) / 1e9
        if end is None or s + d > end:
            end, prev = s + d, name
    mean_busy = sum(busy.values()) / len(busy)

    def ranked(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"window_s": window, "busy_s": mean_busy,
            "busy_s_per_chip": busy,
            "idle_share": 1.0 - mean_busy / window,
            "device_ops": ranked(by_op), "idle_gaps": ranked(gaps)}


def share_of(events, pred):
    """Summed duration of the device events whose full name satisfies
    `pred`, as a share of all device events' summed duration."""
    total = hit = 0
    for evs in events["devices"].values():
        for name, _, d in evs:
            total += d
            if pred(name):
                hit += d
    return hit / total if total else None
