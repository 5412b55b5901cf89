"""The serving benchmark's own arithmetic, on the CPU: the traffic generator,
percentiles and rates, the trace reduction on a recorded trace, the work
model against hand counts, the manifest against the contract's rules."""

import copy
import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "serving"
sys.path.insert(0, str(BENCH))

import manifest as M  # noqa: E402
import stats  # noqa: E402
import tokenizer  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
import work_model  # noqa: E402

CHAT = {**json.loads((BENCH / "mixes/chat-paced.json").read_text()), "rate_rps": 1.5}
DOCQA = json.loads((BENCH / "mixes/docqa-closed.json").read_text())
CHAT_CLOSED = json.loads((BENCH / "mixes/chat-closed.json").read_text())
MISTRAL = json.loads((BENCH / "configs/mistral-7b-int8.json").read_text())
MIXTRAL = json.loads((BENCH / "configs/mixtral-8x7b-int8-1chip.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
LLAMA = M.load_family(MISTRAL)  # both configurations are of the one family


# -- traffic ------------------------------------------------------------------


def _window(specs):
    return [s for s in specs if s.phase == "window"]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_open_loop_offers_the_same_lengths_on_every_seed(seed):
    base = _window(traffic.open_loop(CHAT, 1, 51, 32000))
    mine = _window(traffic.open_loop(CHAT, seed, 51, 32000))
    assert len(mine) == round(1.5 * 51)
    assert sorted(len(s.prompt_ids) for s in mine) == sorted(len(s.prompt_ids) for s in base)
    assert sorted(s.max_tokens for s in mine) == sorted(s.max_tokens for s in base)
    assert all(0 <= s.due_s < 51 for s in mine)
    assert [s.due_s for s in mine] == sorted(s.due_s for s in mine)
    n = len(mine)
    grid = np.array([-math.log(1 - (i + 0.5) / n) for i in range(n)])
    grid = set((grid * 51 / grid.sum()).round(6))
    seen = np.diff([s.due_s for s in mine]).round(6)
    assert set(seen) <= grid and len(set(seen)) == n - 1  # one gap wraps round


def test_open_loop_seed_changes_order_schedule_and_tokens():
    a = _window(traffic.open_loop(CHAT, 1, 51, 32000))
    b = _window(traffic.open_loop(CHAT, 2, 51, 32000))
    again = _window(traffic.open_loop(CHAT, 1, 51, 32000))
    assert [s.prompt_ids for s in a] == [s.prompt_ids for s in again]
    assert [s.due_s for s in a] == [s.due_s for s in again]
    assert [len(s.prompt_ids) for s in a] != [len(s.prompt_ids) for s in b]
    assert [s.due_s for s in a] != [s.due_s for s in b]
    assert a[0].prompt_ids != b[0].prompt_ids


def test_open_loop_ramp_lengths_and_shared_prefix():
    specs = traffic.open_loop(CHAT, 3, 51, 32000)
    ramp = [s for s in specs if s.phase == "ramp"]
    assert len(ramp) == round(1.5 * CHAT["ramp_s"])
    assert all(-CHAT["ramp_s"] <= s.due_s < 0 for s in ramp)
    heads = {tuple(s.prompt_ids[:64]) for s in specs if len(s.prompt_ids) >= 64}
    assert 1 <= len(heads) <= CHAT["shared_prefix"]["variants"]
    lengths = [len(s.prompt_ids) for s in _window(specs)]
    assert min(lengths) >= CHAT["prompt"]["min"] and max(lengths) <= CHAT["prompt"]["max"]
    greedy = [s for s in specs if s.temperature == 0.0]
    assert len(greedy) == math.ceil(len(specs) / CHAT["greedy_every"])
    assert min(min(s.prompt_ids) for s in specs) >= tokenizer.FIRST_PLAIN_ID


def test_quantile_grid_is_fixed_and_spans_the_distribution():
    grid = traffic.quantile_grid(CHAT["prompt"], 200)
    assert grid == sorted(grid) and grid == traffic.quantile_grid(CHAT["prompt"], 200)
    assert abs(np.median(grid) - 192) < 8
    assert grid[0] >= 32 and grid[-1] <= 1024 and grid[-1] > 700


def test_balanced_order_puts_every_stratum_in_every_block():
    rng = np.random.default_rng(0)
    order = traffic.balanced_order(64, 8, rng)
    assert sorted(order) == list(range(64))
    for start in range(0, 64, 8):
        assert sorted(r // 8 for r in order[start:start + 8]) == list(range(8))


def _doc_len(session):
    """Tokens the session's first two prompts share: its document."""
    first, second = session[0].prompt_ids, session[1].prompt_ids
    return next(i for i, (x, y) in enumerate(zip(first, second)) if x != y)


@pytest.mark.parametrize("seed", [1, 99, 2**31 + 12345])
def test_closed_loop_sessions_share_the_document(seed):
    sessions = traffic.closed_loop(DOCQA, seed, 32000)
    base = traffic.closed_loop(DOCQA, 5, 32000)
    assert len(sessions) == DOCQA["session"]["sessions"]
    for sess in sessions[:8]:
        doc_len = _doc_len(sess)
        assert len(sess) == 4 and 2112 <= doc_len <= 3072
        assert len({tuple(r.prompt_ids[:doc_len]) for r in sess}) == 1
        assert all(24 <= len(r.prompt_ids) - doc_len <= 64 for r in sess)
    docs = {tuple(s[0].prompt_ids[:2112]) for s in sessions}
    assert len(docs) == DOCQA["session"]["pool"]

    def lengths(sess_list):
        docs = [_doc_len(s) for s in sess_list]
        questions = [len(r.prompt_ids) - d for s, d in zip(sess_list, docs) for r in s]
        answers = [r.max_tokens for s in sess_list for r in s]
        return docs, questions, answers

    # every seed offers the same multiset of lengths, in another order
    mine, theirs = lengths(sessions), lengths(base)
    for a, b in zip(mine, theirs):
        assert sorted(a) == sorted(b) and a != b
    assert sessions[0][0].prompt_ids != base[0][0].prompt_ids
    again = traffic.closed_loop(DOCQA, seed, 32000)
    assert [[r.prompt_ids for r in s] for s in again] == [[r.prompt_ids for r in s] for s in sessions]


@pytest.mark.parametrize("seed", [1, 99])
def test_closed_loop_order_is_balanced_on_every_seed(seed):
    """Any 8 consecutive sessions hold one document from each eighth of the
    pool's lengths: a stretch of a run carries the same work on every seed."""
    sessions = traffic.closed_loop(DOCQA, seed, 32000)
    pool = DOCQA["session"]["pool"]
    grid = traffic.quantile_grid(DOCQA["session"]["document"], pool)
    assert len(set(grid)) == pool
    for start in range(0, len(sessions), 8):
        eighths = sorted(grid.index(_doc_len(s)) * 8 // pool for s in sessions[start:start + 8])
        assert eighths == list(range(8))


def test_client_starts_are_an_even_grid_in_seeded_order():
    a, b = traffic.client_starts(DOCQA, 1), traffic.client_starts(DOCQA, 2)
    assert len(a) == 16 and max(a) < DOCQA["stagger_s"]
    assert sorted(a) == sorted(b) == [DOCQA["stagger_s"] * k / 16 for k in range(16)]
    assert a != b and a == traffic.client_starts(DOCQA, 1)


def test_no_mix_or_cell_file_has_a_key_nothing_reads():
    """A data file's keys are the generator's and the harness's parameters."""
    read = {"loop", "why", "prompt", "output", "shared_prefix", "temperature", "greedy_every",
            "balance_block", "ramp_s", "trace_s", "check_samples", "rate_rps", "gaps",
            "clients", "session", "stagger_s"}
    for path in [*(BENCH / "mixes").glob("*.json"), *(BENCH / "cells").glob("*.json")]:
        assert set(json.loads(path.read_text())) <= read, path.name


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_gamma_gaps_are_a_fixed_bursty_multiset(seed):
    """A mix names its arrival law: gamma gaps with cv 2 at the same mean
    rate, the same multiset on every seed, filling the window exactly."""
    burst = {**json.loads((BENCH / "mixes/chat-burst.json").read_text()), "rate_rps": 1.5}
    assert burst["gaps"] == {"dist": "gamma", "cv": 2.0}
    mine = _window(traffic.open_loop(burst, seed, 51, 32000))
    base = _window(traffic.open_loop(burst, 1, 51, 32000))
    plain = _window(traffic.open_loop(CHAT, seed, 51, 32000))
    assert len(mine) == len(plain) == round(1.5 * 51)
    assert all(0 <= s.due_s < 51 for s in mine)
    gaps = np.sort(np.diff([s.due_s for s in mine]))
    n = len(mine)
    grid = traffic.gap_grid(burst["gaps"], n)
    grid = np.sort(grid * 51 / grid.sum())
    # the n - 1 gaps inside the window are the grid less the one that wraps round
    assert all(np.isclose(grid, g, rtol=1e-9, atol=1e-12).any() for g in gaps)
    assert abs(grid.sum() - 51) < 1e-9
    cv = grid.std() / grid.mean()
    assert 1.6 < cv < 2.2  # a grid of 76 points clips the far tail
    plain_gaps = np.diff([s.due_s for s in plain])
    assert 0.8 < plain_gaps.std() / plain_gaps.mean() < 1.1
    assert sorted(len(s.prompt_ids) for s in mine) == sorted(len(s.prompt_ids) for s in base)
    with pytest.raises(ValueError):
        traffic.gap_grid({"dist": "pareto"}, 10)


def test_prompt_lengths_cover_what_the_mix_sends():
    sent = {1 + len(s.prompt_ids) for s in traffic.open_loop(CHAT, 4, 51, 32000)}
    assert sent <= set(traffic.prompt_lengths(CHAT, 51))
    closed = traffic.prompt_lengths(DOCQA, 51)
    longest = max(1 + len(r.prompt_ids) for s in traffic.closed_loop(DOCQA, 4, 32000) for r in s)
    assert max(closed) >= longest and min(closed) > 2048  # every prompt takes the chunked path


@pytest.mark.parametrize("seed", [5, 2**31 + 77])
def test_closed_chat_sends_one_multiset_and_warms_every_bucket_it_uses(seed):
    """A closed-loop mix whose prompts spread over the prefill buckets: the
    warm-up has one request for each bucket a prompt can fall into, so that
    nothing compiles in the window."""
    import run

    sessions = traffic.closed_loop(CHAT_CLOSED, seed, 32000)
    base = traffic.closed_loop(CHAT_CLOSED, 1, 32000)
    assert all(len(s) == 1 for s in sessions) and len(sessions) == 720
    sent = [1 + len(s[0].prompt_ids) for s in sessions]
    assert sorted(sent) == sorted(1 + len(s[0].prompt_ids) for s in base)
    assert sorted(s[0].max_tokens for s in sessions) == sorted(s[0].max_tokens for s in base)
    assert len({tuple(s[0].prompt_ids[:64]) for s in sessions}) == 4  # the system prompts
    assert 80 <= min(sent) and max(sent) <= 1024 and 180 < np.median(sent) < 205
    assert set(sent) <= set(traffic.prompt_lengths(CHAT_CLOSED, 51))
    buckets = [128, 256, 512, 1024, 2048]
    warm = [1 + len(r.prompt_ids)
            for r in run.warmup_requests(CHAT_CLOSED, 51, {"prefill_buckets": buckets}, 32000)]
    bucket_of = lambda n: min(b for b in buckets if n <= b)  # noqa: E731
    assert {bucket_of(n) for n in sent} == {bucket_of(n) for n in warm} == {128, 256, 512, 1024}
    assert len(warm) == 4


def test_req_s_counts_a_request_by_the_share_of_its_flight_in_the_window():
    from types import SimpleNamespace as NS

    sys.path.insert(0, str(BENCH / "layers"))
    import end_to_end

    def outcome(pieces, sent, done, ok=True):
        return NS(pieces=pieces, sent_t=sent, done_t=done, ok=ok)

    run = NS(
        times={"window_open": 10.0, "window_close": 20.0},
        outcomes=[
            outcome([(11.0, 4), (12.0, 4)], 10.5, 12.0),  # whole: 1
            outcome([(9.0, 5), (10.0, 5)], 8.0, 10.0),  # over as the window opens: 0
            outcome([(19.5, 1), (20.0, 1), (21.0, 2)], 19.0, 21.0),  # half its flight: 0.5
            outcome([(9.0, 1), (25.0, 1)], 5.0, 25.0),  # the window is half its flight: 0.5
            outcome([(15.0, 3)], 14.0, 16.0, ok=False),  # failed: 0, though tokens came
            outcome([], 19.9, None, ok=False),  # never answered
        ],
    )
    assert end_to_end.req_s(run) == pytest.approx(2.0 / 10.0)
    assert end_to_end.out_tok_s(run) == pytest.approx((8 + 5 + 1 + 3) / 10.0)
    assert end_to_end.METRICS["req_obs_s"] is end_to_end.req_s
    assert end_to_end.METRICS["out_tok_obs_s"] is end_to_end.out_tok_s


def test_tokenizer_round_trip_over_the_vocabulary():
    tok = tokenizer.IdTokenizer(32000)
    ids = [3, 31, 32, 1023, 1024, 31999, 17]
    assert tokenizer.ids_of(tokenizer.text_of(ids)) == ids
    assert tok.encode(tokenizer.text_of(ids)) == [1] + ids
    assert tok.decode(ids) == tokenizer.text_of(ids) and len(tok.decode(ids)) == 3 * len(ids)
    chat = tok.apply_chat_template(
        [{"role": "system", "content": tokenizer.text_of(ids[:3])},
         {"role": "user", "content": tokenizer.text_of(ids[3:])}]
    )
    assert tok.encode(chat, add_bos=False) == ids
    assert tok.eos_id >= tok.vocab_size


# -- statistics ---------------------------------------------------------------


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([10, 20, 30, 40, 50, 60, 70, 80, 90, 100, 110], 90, 100.0),
    ([1, 2, math.inf], 50, 2.0),
    ([1, math.inf, math.inf], 50, math.inf),
    ([1, 2, 3, math.inf], 90, math.inf),
    ([], 50, None),
])
def test_percentile_counts_failures_as_inf(values, q, want):
    assert stats.percentile(values, q) == want


def test_rates_tpot_spread_and_histogram_quantile():
    assert stats.rate(510, 51) == 10.0
    assert stats.tpot_ms(1.0, 2.0, 11) == 100.0
    assert stats.tpot_ms(1.0, 1.0, 1) is None
    runs = [100, 101, 102, 103, 104, 105]
    assert stats.spread(runs) == pytest.approx(3.5 / 102.5)
    cumulative = [(0.1, 10.0), (0.2, 30.0), (0.5, 40.0), (math.inf, 40.0)]
    assert stats.histogram_quantile(cumulative, 0.5) == pytest.approx(0.15)
    assert stats.histogram_quantile([(0.1, 0.0), (math.inf, 0.0)], 0.5) is None


# -- the trace reduction ------------------------------------------------------


RECORDED = json.loads((Path(__file__).parent / "recorded_trace.json").read_text())


def test_reduction_of_a_small_hand_made_trace():
    chip = {
        "modules": [("jit_a(1)", 0.0, 1.0), ("jit_b(2)", 2.0, 1.0)],
        "ops": [("x", 0.0, 0.5), ("y", 0.25, 0.5), ("x", 2.0, 1.0)],
    }
    got = trace_reduce.reduce_events({"chips": {"/device:TPU:0": chip}, "lines": {}})
    assert got["busy_s"] == pytest.approx(1.75)  # 0..0.75 and 2..3: overlaps once
    assert got["window_s"] == pytest.approx(3.0)
    assert got["device_ops"][0] == ["x", pytest.approx(1.5)]
    assert got["idle_gaps"] == [["jit_a_-_jit_b", pytest.approx(1.25)]]
    assert got["programs"]["jit_a"] == {"time_s": pytest.approx(1.0), "count": 1}


def test_reduction_of_the_recorded_chip_trace():
    got = trace_reduce.reduce_events(RECORDED)
    assert got["chips"] == 1
    assert 0 < got["busy_s"] <= got["window_s"]
    assert len(got["device_ops"]) == 10 and len(got["idle_gaps"]) <= 5
    ranked = [s for _n, s in got["device_ops"]]
    assert ranked == sorted(ranked, reverse=True)
    assert any(k.startswith("jit__decode_block_fn") for k in got["programs"])
    total = sum(d for _n, _s, d in RECORDED["chips"]["/device:TPU:0"]["ops"])
    assert sum(ranked) <= total * (1 + 1e-9)


def test_busy_beyond_the_window_is_a_bug_not_a_result():
    peaks = work_model.peaks_for("TPU v5 lite")
    work = LLAMA.decode_step(MISTRAL, 16, 16 * 1000)
    with pytest.raises(AssertionError):
        work_model.roofline_pct(work, 1e-3, peaks)  # faster than the HBM allows
    assert 0 < work_model.roofline_pct(work, 54e-3, peaks) < 100
    with pytest.raises(KeyError):
        work_model.peaks_for("TPU v9")


# -- the work model against hand counts ---------------------------------------


def test_mistral_decode_step_by_hand():
    s = LLAMA.sizes(MISTRAL)
    attn = 4096 * 128 * (32 + 16) + 32 * 128 * 4096
    mlp = 3 * 4096 * 14336
    assert LLAMA.attn_params(s) == attn == 41_943_040
    assert LLAMA.expert_params(s) == mlp == 176_160_768
    active = 32 * (attn + mlp) + 4096 * 32000
    assert LLAMA.active_params_per_token(s) == active == 7_110_393_856
    work = LLAMA.decode_step(MISTRAL, 8, 8 * 500)
    kv_token = 2 * 32 * 8 * 128 * 2  # K and V, 32 layers, 8 heads of 128, bf16
    assert LLAMA.kv_bytes_per_token(s) == kv_token == 131_072
    assert work["flops"] == 2 * active * 8 + 4 * 32 * 32 * 128 * 4000
    assert work["bytes"] == active * 1.0 + kv_token * 4008 + 8 * 4096 * 2
    least, bound = work_model.least_seconds(work, work_model.peaks_for("TPU v5 lite"))
    assert bound == "bytes" and least == pytest.approx(work["bytes"] / 819e9)


def test_mixtral_decode_step_streams_only_the_experts_reached():
    s = LLAMA.sizes(MIXTRAL)
    L = s["L"]
    assert L == 7 and s["E"] == 8 and s["k"] == 2
    assert LLAMA.experts_reached(s, 1) == pytest.approx(2.0)
    assert LLAMA.experts_reached(s, 16) == pytest.approx(8 * (1 - 0.75**16))
    attn, expert = 41_943_040, 176_160_768
    active = L * (attn + 2 * expert + 4096 * 8) + 4096 * 32000
    assert LLAMA.active_params_per_token(s) == active
    one = LLAMA.decode_step(MIXTRAL, 1, 100)
    want = L * (attn + 2 * expert + 4096 * 8 * 2.0) + 4096 * 32000
    want += 2 * L * 8 * 128 * 2 * 101 + 4096 * 2
    assert one["bytes"] == pytest.approx(want)


def test_prefill_counts_causal_attention_and_reads_weights_once_a_call():
    work = LLAMA.prefill(MISTRAL, [2048], 1)
    s = LLAMA.sizes(MISTRAL)
    body = LLAMA.active_params_per_token(s) - 4096 * 32000
    assert work["flops"] == pytest.approx(
        2 * body * 2048 + 2 * 4096 * 32000 + 4 * 32 * 32 * 128 * 2048 * 2049 / 2
    )
    two = LLAMA.prefill(MISTRAL, [2048], 2)
    assert two["bytes"] - work["bytes"] == pytest.approx(LLAMA.weight_bytes(s, 1024))


# -- the numbers `correct` is decided on --------------------------------------


def test_gap_stats_leave_out_near_tied_routing_and_count_the_rest():
    import reference

    gaps = np.array([0.0] * 90 + [0.02] * 4 + [3.0] * 6)
    margins = np.array([0.5] * 94 + [0.001] * 5 + [0.2])  # one wide gap is decided
    got = reference.gap_stats(gaps, margins, "served")
    assert got["served_gap_max"] == 3.0
    assert got["served_gap_p90"] == pytest.approx(0.002)  # between the 90th and 91st of 100
    assert got["served_gap_mean"] == pytest.approx((4 * 0.02 + 18.0) / 100)
    assert got["served_wide_decided_pct"] == pytest.approx(1.0)
    dense = reference.gap_stats(gaps, np.full(100, np.inf), "control")
    assert dense["control_wide_decided_pct"] == pytest.approx(6.0)


@pytest.mark.parametrize("config", [MISTRAL, MIXTRAL], ids=lambda c: c["name"])
def test_every_limit_names_a_number_the_reference_gives(config):
    import reference

    given = set(reference.gap_stats(np.zeros(3), np.ones(3), "served"))
    assert set(config["check"]) <= given and config["check"]
    assert all(limit > 0 for limit in config["check"].values())


# -- the manifest -------------------------------------------------------------


def test_manifest_meets_the_contract_rules():
    assert M.problems(MANIFEST, ROOT) == []
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    readers = M.load_readers()
    for metric in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert M.quantity(metric["name"]) in readers, metric["name"]
    for cell in MANIFEST["workloads"]:
        info = M.resolve(MANIFEST, cell["name"], ROOT)
        reported = {m["name"] for m in info["end_to_end"]}
        assert info["mix"]["loop"] == "closed" and info["mix"]["clients"] > 0
        reason = cell["traffic"] == "reason-closed"
        assert reported == {"setup_s", "out_tok_s" if reason else "req_s"}
        variants = {m["name"].rpartition(".")[0] for m in info["per_layer"]}
        assert variants == {"", "reason" if reason else "closed"}
        # a quantity read in both kinds of cell has a variant for each, and a
        # cell reports each quantity once
        quantities = [M.quantity(m["name"]) for m in info["per_layer"]]
        assert len(set(quantities)) == len(quantities)
        assert {"decode_dev_ms", "decode_roofline", "device_idle_pct", "boot_s"} <= set(quantities)


def test_a_metric_finds_its_reader_by_its_quantity():
    assert M.quantity("closed.decode_roofline") == "decode_roofline"
    assert M.quantity("decode_roofline") == "decode_roofline"
    assert M.quantity("reason.ttft_p50_obs_ms") == "ttft_p50_obs_ms"


@pytest.mark.parametrize("mutate,needle", [
    (lambda m: m["workloads"][0].update(name="has space"), "bad name"),
    (lambda m: m["end_to_end"][0].update(unit="tokens per second"), "bad unit"),
    (lambda m: m["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda m: m["per_layer"][0].update(moves="nothing"), "no such end-to-end"),
    (lambda m: m["per_layer"][0].update(why="because"), "keys"),
    (lambda m: m["configs"][1].update(reduced=["hidden_size"]), "width"),
    (lambda m: m.update(run_seconds=52), "run_seconds"),
    (lambda m: m["workloads"].append(dict(m["workloads"][0], name="again")), "twice"),
    (lambda m: m["end_to_end"].pop(), "setup_s"),
])
def test_manifest_check_refuses(mutate, needle):
    broken = copy.deepcopy(MANIFEST)
    mutate(broken)
    assert any(needle in p for p in M.problems(broken, ROOT)), M.problems(broken, ROOT)
