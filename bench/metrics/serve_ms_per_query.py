"""The benchmark's timer around ``StoreQueryEngine.serve``, summed over
the window's waves, per query served (ms)."""


def read(run):
    return 1e3 * run.window.serve_s / run.n_queries
