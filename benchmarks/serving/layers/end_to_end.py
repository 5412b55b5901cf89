"""What a user of the endpoint sees, read at the client. The ``_obs`` names
are the same numbers reported as per-layer observations in cells where their
run-to-run spread does not fit an end-to-end bound (PERF.md says which)."""

import stats


def _finite(x):
    return None if x is None or x == float("inf") else x


def ttft(q):
    return lambda run: _finite(stats.percentile(run.ttfts_ms(), q))


def tpot(q):
    return lambda run: _finite(stats.percentile(run.tpots_ms(), q))


def out_tok_s(run):
    """Output tokens that reached a client inside the window, over the
    window: all the work and all the time of the window, whichever request
    a token belongs to."""
    t0, t1 = run.times["window_open"], run.times["window_close"]
    tokens = sum(n for o in run.outcomes for t, n in o.pieces if t0 <= t < t1)
    return stats.rate(tokens, t1 - t0)


def setup_s(run):
    return run.times["window_open"] - run.times["process_start"]


# ``ttft_p50_ms`` and ``tpot_p90_ms`` are in no cell's end-to-end list today
# (PERF.md section 2); the readers stay so that a manifest entry alone
# brings them back.
METRICS = {
    "ttft_p50_ms": ttft(50), "ttft_p50_obs_ms": ttft(50), "ttft_p90_obs_ms": ttft(90),
    "tpot_p50_ms": tpot(50), "tpot_p50_obs_ms": tpot(50),
    "tpot_p90_ms": tpot(90), "tpot_p90_obs_ms": tpot(90),
    "out_tok_s": out_tok_s,
    "setup_s": setup_s,
}
