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


def req_s(run):
    """Requests served per second of the window: every request in flight
    during the window counts by the share of its time in flight (sent to
    done) that lies inside it, so one that straddles an edge counts in part
    and none moves the rate by a whole request. In a closed loop, where a
    client is always in flight, this is the rate at which the clients get
    through their requests (clients over the time-averaged latency). All the
    work and all the time of the window, in requests where ``out_tok_s`` is
    in tokens: where a request's cost is mostly its prompt, how long the
    answers of the requests in one window happen to be moves the tokens and
    hardly the requests (PERF.md section 2). A failed request counts 0."""
    t0, t1 = run.times["window_open"], run.times["window_close"]
    served = sum(
        max(0.0, min(t1, o.done_t) - max(t0, o.sent_t)) / (o.done_t - o.sent_t)
        for o in run.outcomes
        if o.ok and o.sent_t is not None and o.done_t is not None and o.done_t > o.sent_t
    )
    return stats.rate(served, t1 - t0)


def setup_s(run):
    return run.times["window_open"] - run.times["process_start"]


# ``ttft_p50_ms``, ``tpot_p50_ms`` and ``tpot_p90_ms`` are in no cell's
# end-to-end list today (PERF.md section 2); the readers stay so that a
# manifest entry alone brings them back.
METRICS = {
    "ttft_p50_ms": ttft(50), "ttft_p50_obs_ms": ttft(50), "ttft_p90_obs_ms": ttft(90),
    "tpot_p50_ms": tpot(50), "tpot_p50_obs_ms": tpot(50),
    "tpot_p90_ms": tpot(90), "tpot_p90_obs_ms": tpot(90),
    "out_tok_s": out_tok_s, "out_tok_obs_s": out_tok_s,
    "req_s": req_s, "req_obs_s": req_s,
    "setup_s": setup_s,
}
