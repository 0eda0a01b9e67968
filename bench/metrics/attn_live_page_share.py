"""Kernel: share of the paged kernel's table walk that the decoding
slots' pages fill, from the engine's counters over the window's steps:
attn_pages_live / attn_pages_table (each span adds its steps x the pages
its decoding slots hold, and its steps x slots x the exported table's
width). The kernel fetches those pages only. A program without the
counters reads nothing."""


def read(run):
    steps = run.window_steps()
    if len(steps) < 2:
        return None
    a, b = steps[0].stats, steps[-1].stats
    if "attn_pages_table" not in a or "attn_pages_table" not in b:
        return None
    table = b["attn_pages_table"] - a["attn_pages_table"]
    if table <= 0:
        return None
    return 100.0 * (b["attn_pages_live"] - a["attn_pages_live"]) / table
