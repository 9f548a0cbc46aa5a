"""out_tok_s: output tokens emitted inside the window, by all requests,
divided by the window's length."""


def read(run):
    n = sum(1 for s in run.served for t in s.token_times()
            if run.t0 <= t <= run.t1)
    return n / (run.t1 - run.t0)
