"""Many small experts, every one held, beside a gated short convolution:
what the program adds for such a model, read where it writes it.

``conv_mix_dev_pct`` is the share of the traced device time under the scope
``mtpu.conv_mix`` (a convolution mixer's two projections, its gates, its
taps and its window, prefill and decode alike). ``expert_tile_fill_pct`` is
the share of the rows of the routed experts' tiles, over the window's decode
blocks, that held a real (token, expert) pair
(``mtpu_expert_tile_rows_total{kind}``: ``pairs`` / ``rows``, counted on the
device from the route's ids and read with the block's tokens): with a few
pairs an expert a decode step's tiles run nearly empty, and the tile loop's
products are mostly padding.

A program that writes no such scope or series (a commit before them, a model
that routes nothing or holds a share of its experts) reads None, never 0,
and the result line leaves the metric out.
"""

ROWS = "mtpu_expert_tile_rows_total"


def conv_mix_dev_pct(run):
    scopes = (run.trace or {}).get("scopes")
    if not scopes or "mtpu.conv_mix" not in scopes:
        return None
    total = sum(row["time_s"] for row in scopes.values())
    return 100.0 * scopes["mtpu.conv_mix"]["time_s"] / total if total else None


def expert_tile_fill_pct(run):
    if ROWS not in run.counters_close:
        return None
    rows = run.counter_delta(ROWS, kind="rows")
    return 100.0 * run.counter_delta(ROWS, kind="pairs") / rows if rows > 0 else None


METRICS = {
    "conv_mix_dev_pct": conv_mix_dev_pct,
    "expert_tile_fill_pct": expert_tile_fill_pct,
}
