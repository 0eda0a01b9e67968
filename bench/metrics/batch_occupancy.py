"""Engine host loop: share of decode slots that produced a token, from
the engine's counters over the window's steps:
decode_tokens / (decode_steps x slots)."""


def read(run):
    steps = run.window_steps()
    if len(steps) < 2:
        return None
    a, b = steps[0].stats, steps[-1].stats
    n_steps = b["decode_steps"] - a["decode_steps"]
    if n_steps <= 0:
        return None
    toks = b["decode_tokens"] - a["decode_tokens"]
    return 100.0 * toks / (n_steps * run.ecfg.slots)
