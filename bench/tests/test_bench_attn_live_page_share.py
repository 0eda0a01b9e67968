"""``attn_live_page_share`` on fabricated runs: the engine's counters
over the window's steps, and nothing from a program without them."""
from types import SimpleNamespace

from bench.metrics import attn_live_page_share


def _run(*stats):
    steps = [SimpleNamespace(stats=s) for s in stats]
    return SimpleNamespace(window_steps=lambda: steps)


def test_share_over_the_window():
    run = _run({"attn_pages_table": 100, "attn_pages_live": 40},
               {"attn_pages_table": 300, "attn_pages_live": 90},
               {"attn_pages_table": 500, "attn_pages_live": 140})
    assert attn_live_page_share.read(run) == 100.0 * 100 / 400


def test_program_without_the_counters_reads_nothing():
    run = _run({"decode_steps": 8, "decode_tokens": 20},
               {"decode_steps": 16, "decode_tokens": 44})
    assert attn_live_page_share.read(run) is None


def test_window_without_decoding_reads_nothing():
    still = {"attn_pages_table": 64, "attn_pages_live": 16}
    assert attn_live_page_share.read(_run(still, dict(still))) is None
    assert attn_live_page_share.read(_run(still)) is None
