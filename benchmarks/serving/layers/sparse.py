"""A learned sparse attention (an indexer, an exact top-k, attention over the
selected positions): what the program adds for such a model, read where it
writes it.

``indexer_dev_pct`` and ``topk_select_dev_pct`` are the shares of the traced
device time under the scopes ``mtpu.indexer`` (the index projections, the
gather of the indexer's cached keys, the index scores) and
``mtpu.topk_select`` (the exact top-k of the scores). ``sparse_selected_pct``
is ``selected`` over ``attended`` of ``mtpu_sparse_positions_total{kind}``
over the window: 100 where the attention programs read only what was
selected (a gathered form), the selected share of the causal positions where
they compute every one under a mask. ``indexer_roofline`` and
``sparse_attention_roofline`` hold the device time under ``mtpu.indexer`` and
``mtpu.attention`` against the least the chip could take for the work the
window needed there, as the configuration's family counts it
(``SCOPE_WORK``): for each prompt prefilled in the window, every query's
``t + 1`` scored and ``min(t + 1, index_topk)`` attended positions; for the
window's decode steps, each live sequence's context scored and
``min(context, index_topk)`` of it attended.

A program that writes no such scope or series (a commit before them, a model
without an indexer) reads None, never 0, and the result line leaves the
metric out.
"""

import manifest
import work_model

POSITIONS = "mtpu_sparse_positions_total"


def _scope(run, part):
    """(device seconds under ``mtpu.<part>``, of all operations), or None."""
    scopes = (run.trace or {}).get("scopes")
    if not scopes or f"mtpu.{part}" not in scopes:
        return None
    return scopes[f"mtpu.{part}"]["time_s"], sum(row["time_s"] for row in scopes.values())


def _dev_pct(part):
    def reader(run):
        got = _scope(run, part)
        return 100.0 * got[0] / got[1] if got and got[1] else None
    return reader


def sparse_selected_pct(run):
    if POSITIONS not in run.counters_close:
        return None
    attended = run.counter_delta(POSITIONS, kind="attended")
    return 100.0 * run.counter_delta(POSITIONS, kind="selected") / attended if attended > 0 else None


def _phases(run):
    """What the window's prefill calls and decode steps ran over, or None
    where the run lacks what says so: (prompt lengths, prefill calls) and
    (decode steps, mean batch, mean context), each scaled from the traced
    part to the window as ``prefill_roofline`` does."""
    scale = (run.times["window_close"] - run.times["window_open"]) / run.trace["window_s"]
    prompts, prefill = run.prefilled_prompts(), run.program("prefill")
    decode, batch = run.program("decode"), run.decode_batch_mean()
    done = [o for o in run.scored if o.ok and o.prompt_tokens]
    pre = (prompts, prefill[1] * scale) if prefill and prompts else None
    dec = None
    if decode and batch and done:
        steps = decode[1] * int(run.device["decode_block"]) * scale
        dec = (steps, batch, sum(o.prompt_tokens + o.n_out / 2.0 for o in done) / len(done))
    return scale, pre, dec


def _roofline(part, work_of_phases):
    def reader(run):
        got = _scope(run, part)
        topk = run.config.get("index_topk")
        table = getattr(manifest.load_family(run.config), "SCOPE_WORK", {})
        work_of = table.get(f"mtpu.{part}")
        if not got or not got[0] or not topk or work_of is None:
            return None
        scale, pre, dec = _phases(run)
        works = [w for w in work_of_phases(run.config, work_of, int(topk), pre, dec) if w]
        if not works:
            return None
        return work_model.roofline_pct(
            works, got[0] * scale, work_model.peaks_for(run.device["kind"])
        )
    return reader


def _selected(n: int, topk: int) -> float:
    """Sum over the queries ``t < n`` of ``min(t + 1, topk)``."""
    short = min(n, topk)
    return short * (short + 1) / 2.0 + max(n - topk, 0) * float(topk)


def _attention_work(config, work_of, topk, pre, dec):
    if pre:
        prompts, calls = pre
        yield work_of(config, float(sum(prompts)), calls, phase="prefill",
                      selected=sum(_selected(n, topk) for n in prompts))
    if dec:
        steps, batch, context = dec
        yield work_of(config, batch * steps, steps, phase="decode",
                      selected=batch * steps * min(context, topk))


def _indexer_work(config, work_of, topk, pre, dec):
    if pre:
        prompts, calls = pre
        yield work_of(config, float(sum(prompts)), calls,
                      scored=sum(n * (n + 1) / 2.0 for n in prompts), keys=float(sum(prompts)))
    if dec:
        steps, batch, context = dec
        yield work_of(config, batch * steps, steps, scored=batch * steps * context,
                      keys=batch * steps * context)


METRICS = {
    "indexer_dev_pct": _dev_pct("indexer"),
    "topk_select_dev_pct": _dev_pct("topk_select"),
    "sparse_selected_pct": sparse_selected_pct,
    "indexer_roofline": _roofline("indexer", _indexer_work),
    "sparse_attention_roofline": _roofline("attention", _attention_work),
}
