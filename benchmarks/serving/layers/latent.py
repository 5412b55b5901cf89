"""A latent (MLA) cache and a share of the routed experts: what the program
adds for such a model, read where it writes it.

``latent_expand_dev_pct`` and ``expert_dispatch_dev_pct`` are the shares of
the traced device time under the scopes ``mtpu.latent_expand`` (latents, the
call's own and a cached prefix's, expanded by ``W_kvb`` to per-head keys and
values for the prefill kernel) and ``mtpu.expert_dispatch`` (routed pairs
sorted into tiles, the tiles' token rows gathered, the results gathered
back). ``attention_roofline`` holds the device time under ``mtpu.attention``
against the least the chip could take for the attention the window needed,
as the configuration's family counts it (``SCOPE_WORK["mtpu.attention"]``):
the causal query-key pairs of the window's prefilled prompts, and the cached
positions its decode steps attended to. ``expert_held_pct`` is the share of
the decode blocks' routed pairs that landed on experts this chip holds
(``mtpu_routed_pairs_total{where}``, counted on the device and read with the
block's tokens).

A program that writes no such scope or series (a commit before them, a
model without a latent cache) reads None, never 0, and the result line
leaves the metric out.
"""

import manifest
import work_model

PAIRS = "mtpu_routed_pairs_total"


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


def attention_roofline(run):
    got = _scope(run, "attention")
    work_of = manifest.load_family(run.config).SCOPE_WORK.get("mtpu.attention")
    if not got or not got[0] or work_of is None:
        return None
    window = run.times["window_close"] - run.times["window_open"]
    scale = window / run.trace["window_s"]
    works = []
    prompts = run.prefilled_prompts()
    prefill = run.program("prefill")
    if prefill and prompts:
        pairs = sum(n * (n + 1) / 2.0 for n in prompts)
        works.append(work_of(run.config, float(sum(prompts)), prefill[1] * scale, pairs=pairs))
    decode, batch = run.program("decode"), run.decode_batch_mean()
    done = [o for o in run.scored if o.ok and o.prompt_tokens]
    if decode and batch and done:
        steps = decode[1] * int(run.device["decode_block"]) * scale
        context = sum(o.prompt_tokens + o.n_out / 2.0 for o in done) / len(done)
        works.append(work_of(run.config, batch * steps, steps, positions=batch * context * steps))
    works = [w for w in works if w]
    if not works:
        return None
    return work_model.roofline_pct(
        works, got[0] * scale, work_model.peaks_for(run.device["kind"])
    )


def expert_held_pct(run):
    if PAIRS not in run.counters_close:
        return None
    held = run.counter_delta(PAIRS, where="held")
    pairs = held + run.counter_delta(PAIRS, where="elsewhere")
    return 100.0 * held / pairs if pairs > 0 else None


METRICS = {
    "latent_expand_dev_pct": _dev_pct("latent_expand"),
    "expert_dispatch_dev_pct": _dev_pct("expert_dispatch"),
    "attention_roofline": attention_roofline,
    "expert_held_pct": expert_held_pct,
}
