"""What one run hands to the metric readers."""

from __future__ import annotations

import dataclasses
import json
import math
import re
from pathlib import Path

import stats

#: the XLA module names of the compiled programs, by kind
PROGRAMS = json.loads((Path(__file__).parent / "layers" / "programs.json").read_text())


@dataclasses.dataclass
class RunData:
    cell: dict
    config: dict
    mix: dict
    times: dict  # process_start, health_ok, warmup_start, window_open, window_close
    outcomes: list  # every request sent, ramp included (loadgen.Outcome)
    scored: list  # the requests the latency percentiles are taken over
    counters_open: dict  # /metrics at the window's opening: name -> [(labels, value)]
    counters_close: dict
    kv_pages_peak: float | None  # polled over the window (traced runs)
    engine_log: dict  # request id -> the engine's own timestamps
    device: dict
    trace: dict | None  # trace_reduce.reduce_events, or None without --trace 1

    @property
    def paced(self) -> bool:
        return self.mix["loop"] == "open"

    def ttfts_ms(self) -> list[float]:
        """Per scored request: first streamed token minus the time it was
        due (open loop) or sent (closed loop); inf for a failed request."""
        return [
            (o.first_t - (o.due_t if o.due_t is not None else o.sent_t)) * 1000.0
            if o.ok and o.first_t is not None else math.inf
            for o in self.scored
        ]

    def tpots_ms(self) -> list[float]:
        out = []
        for o in self.scored:
            if not o.ok:
                out.append(math.inf)
                continue
            t = stats.tpot_ms(o.first_t, o.last_t, o.n_out)
            if t is not None:
                out.append(t)
        return out

    def program(self, kind: str) -> tuple[float, float] | None:
        """(device seconds, calls) of the programs of one kind (``decode``,
        ``prefill``: patterns in ``layers/programs.json``) in the trace."""
        if not self.trace:
            return None
        pat = re.compile(PROGRAMS[kind])
        hits = [v for k, v in self.trace["programs"].items() if pat.search(k)]
        if not hits:
            return None
        return sum(v["time_s"] for v in hits), sum(v["count"] for v in hits)

    def prefilled_prompts(self) -> list[int]:
        """Lengths of the prompts whose first token fell inside the window."""
        t0, t1 = self.times["window_open"], self.times["window_close"]
        return [
            e["n_prompt"] for e in self.engine_log.values()
            if e.get("first_token_at") is not None and t0 <= e["first_token_at"] < t1
        ]

    def decode_batch_mean(self) -> float | None:
        """Decode tokens over decode steps in the window: generated tokens
        less the first token of each request, which its prefill produced."""
        steps = self.counter_delta("mtpu_decode_steps_total")
        if steps <= 0:
            return None
        firsts = self.counter_delta("mtpu_ttft_seconds_count")
        return (self.counter_delta("mtpu_generated_tokens_total") - firsts) / steps

    def counter_delta(self, name: str, **labels) -> float:
        def total(snapshot):
            return sum(
                v for lab, v in snapshot.get(name, [])
                if all(lab.get(k) == w for k, w in labels.items())
            )
        return total(self.counters_close) - total(self.counters_open)

    def histogram_delta(self, name: str) -> list[tuple[float, float]]:
        def buckets(snapshot):
            acc: dict = {}
            for lab, v in snapshot.get(name + "_bucket", []):
                le = math.inf if lab["le"] in ("+Inf", "inf") else float(lab["le"])
                acc[le] = acc.get(le, 0.0) + v
            return acc
        a, b = buckets(self.counters_open), buckets(self.counters_close)
        return [(le, b[le] - a.get(le, 0.0)) for le in sorted(b)]


def parse_exposition(text: str) -> dict:
    """Prometheus text format -> name -> [(labels, value)]."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = {}
        if rest:
            for part in rest.rstrip("}").split('",'):
                if "=" in part:
                    k, _, v = part.partition("=")
                    labels[k.strip()] = v.strip().strip('"')
        try:
            out.setdefault(name.strip(), []).append((labels, float(value)))
        except ValueError:
            continue
    return out
