"""The reader of ``mtpu_decode_kv_positions_total``
(``benchmarks/serving/layers/decode_kv.py``) on a recorded pair of /metrics
scrapes, None on a pair from a program without the series, and its two
entries in the manifest."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "serving"
sys.path.insert(0, str(BENCH))

import manifest as M  # noqa: E402
from rundata import RunData, parse_exposition  # noqa: E402

READERS = M.load_readers()
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())

# 16 slots x 256 pages x 16 positions = 65536 table positions a step, 256 a
# trip and slot: 100 steps of 2 trips before the window, then 400 of 3 and
# 100 of 16 (the whole table) inside it
OPEN = """
mtpu_decode_steps_total 100
mtpu_decode_kv_positions_total{kind="read"} 819200
mtpu_decode_kv_positions_total{kind="live"} 120000
mtpu_decode_kv_positions_total{kind="table"} 6553600
"""
CLOSE = """
mtpu_decode_steps_total 600
mtpu_decode_kv_positions_total{kind="read"} 12288000
mtpu_decode_kv_positions_total{kind="live"} 2000000
mtpu_decode_kv_positions_total{kind="table"} 39321600
"""
BEFORE_THE_SERIES = "mtpu_decode_steps_total 600\nmtpu_generated_tokens_total 5\n"


def _run(open_text, close_text):
    return RunData(
        cell={}, config={}, mix={"loop": "open"},
        times={"window_open": 100.0, "window_close": 150.0},
        outcomes=[], scored=[], counters_open=parse_exposition(open_text),
        counters_close=parse_exposition(close_text), kv_pages_peak=None,
        engine_log={}, device={}, trace=None,
    )


def test_read_share_of_the_table_over_the_window():
    # (400 x 3 + 100 x 16) trips x 4096 of 500 x 65536
    assert READERS["decode_kv_read_pct"](_run(OPEN, CLOSE)) == pytest.approx(35.0)


def test_a_whole_table_every_step_reads_100():
    close = CLOSE.replace('kind="read"} 12288000', 'kind="read"} 33587200')
    assert READERS["decode_kv_read_pct"](_run(OPEN, close)) == pytest.approx(100.0)


@pytest.mark.parametrize("open_text, close_text", [
    (BEFORE_THE_SERIES, BEFORE_THE_SERIES),  # the parent: no such series
    (OPEN, OPEN),  # no decode step inside the window
])
def test_none_where_there_is_nothing_to_read(open_text, close_text):
    assert READERS["decode_kv_read_pct"](_run(open_text, close_text)) is None


def test_manifest_entries_follow_their_siblings():
    assert M.problems(MANIFEST, ROOT) == []
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for variant in ("reason", "closed"):
        mine = by_name[f"{variant}.decode_kv_read_pct"]
        sibling = by_name[f"{variant}.decode_roofline"]
        for key in ("layer", "moves", "workloads"):
            assert mine[key] == sibling[key]
        assert mine["source"] == "program_counter" and mine["unit"] == "%"
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index("reason.decode_kv_read_pct")  # appended; later PRs append after them
    assert names[at:at + 2] == ["reason.decode_kv_read_pct", "closed.decode_kv_read_pct"]
