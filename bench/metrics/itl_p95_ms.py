"""itl_p95_ms: the 95th percentile (nearest rank) of every gap between two
output tokens of one request that ends inside the window, pooled over all
requests, in milliseconds."""

from bench.harness import percentile


def read(run):
    gaps = []
    for s in run.served:
        t = s.token_times()
        gaps += [b - a for a, b in zip(t, t[1:]) if run.t0 <= b <= run.t1]
    p = percentile(gaps, 95)
    return None if p is None else p * 1e3
