"""decode_slots_mean: the mean number of live slots in the decode steps of
the window: of the engine's ``max_slots`` rows, those that carried a
request's next token rather than padding."""


def read(run):
    live = run.engine.decode_live
    return sum(live) / len(live) if live else None
