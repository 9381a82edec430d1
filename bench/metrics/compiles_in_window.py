"""Programs JAX compiled, or loaded from its persistent cache, while the
window ran."""


def read(run):
    return run.compiles
