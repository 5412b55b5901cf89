"""Scheduler and cache manager, from the engine's counters on /metrics
(deltas over the window) and the API's own usage fields."""

import stats


def queue_wait_p50_ms(run):
    q = stats.histogram_quantile(run.histogram_delta("mtpu_engine_queue_wait_seconds"), 0.5)
    return None if q is None else q * 1000.0


def decode_batch_mean(run):
    return run.decode_batch_mean()


def prefix_hit_pct(run):
    """Prompt tokens served from cached pages over prompt tokens."""
    done = [o for o in run.scored if o.ok and o.prompt_tokens]
    if not done:
        return None
    return 100.0 * sum(o.cached_tokens or 0 for o in done) / sum(o.prompt_tokens for o in done)


def kv_pages_peak_pct(run):
    if run.kv_pages_peak is None:
        return None
    return 100.0 * run.kv_pages_peak / run.device["kv_pages"]


METRICS = {
    "queue_wait_p50_ms": queue_wait_p50_ms, "decode_batch_mean": decode_batch_mean,
    "prefix_hit_pct": prefix_hit_pct, "kv_pages_peak_pct": kv_pages_peak_pct,
}
