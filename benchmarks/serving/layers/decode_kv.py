"""How far the decode step's attention walks the page table.

The program counts, at each decode-block dispatch and for each of the block's
steps, the KV positions the chunked attention loop reads (its trips x the
positions of a trip x slots), the live contexts, and what every slot's whole
table holds (``mtpu_decode_kv_positions_total{kind}``). The reader returns
None where the program exports no such series (a commit that gathers the
whole table: 100% by construction), and the result line leaves it out.
"""

NAME = "mtpu_decode_kv_positions_total"


def decode_kv_read_pct(run):
    """KV positions read over the positions of the whole table, over the
    window's decode steps."""
    table = run.counter_delta(NAME, kind="table")
    if NAME not in run.counters_close or table <= 0:
        return None
    return 100.0 * run.counter_delta(NAME, kind="read") / table


METRICS = {"decode_kv_read_pct": decode_kv_read_pct}
