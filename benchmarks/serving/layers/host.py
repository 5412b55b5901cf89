"""Entry points and executor (boot), the HTTP front, the load generator."""

import stats


def boot_s(run):
    return run.times["health_ok"] - run.times["process_start"]


def warmup_s(run):
    return run.times["window_open"] - run.times["warmup_start"]


def http_ttft_gap_ms(run):
    """Client's first streamed token minus the engine's own first-token
    time, median: what the HTTP front and the stream add."""
    gaps = []
    for o in run.scored:
        e = run.engine_log.get(o.request_id)
        if o.ok and e and e.get("first_token_at") is not None:
            gaps.append((o.first_t - e["first_token_at"]) * 1000.0)
    return stats.percentile(gaps, 50)


def gen_late_p99_ms(run):
    late = [
        (o.sent_t - o.due_t) * 1000.0
        for o in run.scored if o.due_t is not None and o.sent_t is not None
    ]
    return stats.percentile(late, 99)


METRICS = {
    "boot_s": boot_s, "warmup_s": warmup_s,
    "http_ttft_gap_ms": http_ttft_gap_ms, "gen_late_p99_ms": gen_late_p99_ms,
}
