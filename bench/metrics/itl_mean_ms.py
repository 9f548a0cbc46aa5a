"""itl_mean_ms: the mean gap between two output tokens of one request, over
every gap that ends inside the window, pooled over all requests, in
milliseconds: the time per output token a user waits on average, taken
over all the work of the window."""


def read(run):
    gaps = []
    for s in run.served:
        t = s.token_times()
        gaps += [b - a for a, b in zip(t, t[1:]) if run.t0 <= b <= run.t1]
    return sum(gaps) / len(gaps) * 1e3 if gaps else None
