"""Routed experts behind a state-space mixer (Granite-4.0-H-Small's block):
what the program adds for such a model, read where it writes it.

``router_dev_pct`` is the share of the traced device time under the scope
``mtpu.router``: the router's product over its whole width in float32, the
softmax, the top-k and the renormalisation of every routed layer, prefill
and decode alike (72 columns and a top-10 a layer here; the sort of the
pairs into tiles and the gathers are ``mtpu.expert_dispatch``'s). The share
of a decode block's routed pairs that land on experts this chip holds is
``expert_held_pct`` (``layers/latent.py``), read in this family's cell too.

A program that writes no such scope (a commit before it, a model that routes
nothing) reads None, never 0, and the result line leaves the metric out.
"""


def router_dev_pct(run):
    scopes = (run.trace or {}).get("scopes")
    if not scopes or "mtpu.router" not in scopes:
        return None
    total = sum(row["time_s"] for row in scopes.values())
    return 100.0 * scopes["mtpu.router"]["time_s"] / total if total else None


METRICS = {"router_dev_pct": router_dev_pct}
