"""Compiled ``xor_delta`` kernel launches in the window
(``ops.KERNEL_LAUNCHES``), per query."""


def read(run):
    return run.launches["xor_delta"] / run.n_queries
