"""Sliding-window layers beside global ones, over two page groups: what the
program adds for such a model, read where it writes it.

``window_attention_dev_pct`` is the share of the traced device time under
the scope ``mtpu.window_attention`` (the window layers' attention, prefill
and decode, with their gathers of the ring: the program keeps those out of
``mtpu.page_gather``, so the scope is the layers' whole attention; the
global layers' stays under ``mtpu.attention``).
``window_attention_roofline`` holds that time against the least the chip
could take for the work the family counts under the scope
(``SCOPE_WORK["mtpu.window_attention"]``): ``min(t + 1, window)`` query-key
pairs a prefilled query, ``min(context, window)`` cached positions a
sequence and decode step, whatever implements it. ``window_kv_read_pct`` is
what the decode steps read in the window layers over the positions those
sequences held (``mtpu_decode_kv_positions_total{kind, layers}``: ``read`` of
``layers="window"`` over ``live`` of ``layers="global"``, the whole
contexts): under 100 where the window bounds the traffic.
``kv_window_pages_peak_pct`` is the most pages of the window group held at
once over its budget (``mtpu_kv_window_pages_peak`` /
``mtpu_kv_window_pages_total``).

A program that writes no such scope or series (a commit before them, a model
with no window group) reads None, never 0, and the result line leaves the
metric out.
"""

import manifest
import work_model

KV = "mtpu_decode_kv_positions_total"
PEAK, TOTAL = "mtpu_kv_window_pages_peak", "mtpu_kv_window_pages_total"
SCOPE = "mtpu.window_attention"


def _scope(run):
    """(device seconds under the scope, of all operations), or None."""
    scopes = (run.trace or {}).get("scopes")
    if not scopes or SCOPE not in scopes:
        return None
    return scopes[SCOPE]["time_s"], sum(row["time_s"] for row in scopes.values())


def window_attention_dev_pct(run):
    got = _scope(run)
    return 100.0 * got[0] / got[1] if got and got[1] else None


def window_attention_roofline(run):
    got = _scope(run)
    work_of = getattr(manifest.load_family(run.config), "SCOPE_WORK", {}).get(SCOPE)
    if not got or not got[0] or work_of is None:
        return None
    window = run.times["window_close"] - run.times["window_open"]
    scale = window / run.trace["window_s"]
    works = []
    prompts = run.prefilled_prompts()
    prefill = run.program("prefill")
    if prefill and prompts:
        works.append(work_of(run.config, float(sum(prompts)), prefill[1] * scale, lengths=prompts))
    decode, batch = run.program("decode"), run.decode_batch_mean()
    done = [o for o in run.scored if o.ok and o.prompt_tokens]
    if decode and batch and done:
        steps = decode[1] * int(run.device["decode_block"]) * scale
        contexts = [o.prompt_tokens + o.n_out / 2.0 for o in done]
        # the batch's sequences stand at the scored requests' mean contexts
        per_step = batch / len(contexts)
        works.append(work_of(
            run.config, batch * steps, steps, contexts=contexts, steps=steps * per_step,
        ))
    works = [w for w in works if w]
    if not works:
        return None
    return work_model.roofline_pct(
        works, got[0] * scale, work_model.peaks_for(run.device["kind"])
    )


def window_kv_read_pct(run):
    if KV not in run.counters_close:
        return None
    held = run.counter_delta(KV, kind="live", layers="global")
    read = run.counter_delta(KV, kind="read", layers="window")
    return 100.0 * read / held if held > 0 else None


def _gauge(run, name):
    values = [v for _lab, v in run.counters_close.get(name, [])]
    return values[0] if values else None


def kv_window_pages_peak_pct(run):
    peak, total = _gauge(run, PEAK), _gauge(run, TOTAL)
    return 100.0 * peak / total if peak is not None and total else None


METRICS = {
    "window_attention_dev_pct": window_attention_dev_pct,
    "window_attention_roofline": window_attention_roofline,
    "window_kv_read_pct": window_kv_read_pct,
    "kv_window_pages_peak_pct": kv_window_pages_peak_pct,
}
