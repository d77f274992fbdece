"""Order statistics for the benchmark's own timings.  Pure Python, so the
numbers do not depend on a numpy version."""


def percentile(values, q):
    """(q-th percentile, sample count) by linear interpolation between the
    two nearest ranks (numpy's default method).  q in [0, 100]."""
    xs = sorted(values)
    n = len(xs)
    if n == 0:
        raise ValueError("percentile of no samples")
    pos = (n - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, n - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo), n


def median(values):
    return percentile(values, 50)[0]


def pooled_gaps(arrival_times):
    """Gaps between consecutive token arrivals, pooled over requests.
    `arrival_times`: one list of arrival instants per request, one instant
    per token; tokens that arrived in one chunk share an instant, so all
    but the first of them count a gap of 0."""
    gaps = []
    for times in arrival_times:
        gaps.extend(b - a for a, b in zip(times, times[1:]))
    return gaps
