"""State backend: the most pages of the pool in use after any step of
the window, as a share of the pool (``PagePool.n_used`` / ``n_pages``)."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    return 100.0 * max(s.pages_used for s in steps) / run.ecfg.n_pages
