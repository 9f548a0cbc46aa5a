"""setup_s: seconds from the process's start to the window's opening:
imports, weights, the engine, the warm-up of every compile bucket (with
compilation, or loading from JAX's persistent cache) and, in a closed
loop, filling the slots."""


def read(run):
    return run.setup_s
