"""Device time by ``mtpu.*`` scope: the reader of an operation's scope from
the bytes of an ``.xplane.pb`` (``trace_reduce.op_scopes``, the benchmark's
own copy of what ``tpurun profile --xplane`` does), the reduction's ``scopes``
tables on a hand-made trace and on a trace recorded on the chip, and the
readers of ``layers/scopes.py``: None, never 0, where a trace shows no scope.
"""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "serving"
sys.path.insert(0, str(BENCH))

import manifest as M  # noqa: E402
import trace_reduce as T  # noqa: E402
from rundata import RunData  # noqa: E402

HERE = Path(__file__).parent


def _expanded(recording):
    """The recording keeps each operation's name and scope as an index."""
    names, scopes = recording["names"], recording["scopes"]
    return {"lines": {}, "chips": {
        plane: {"modules": chip["modules"],
                "ops": [[names[n], start, dur, scopes[s]] for n, start, dur, s in chip["ops"]]}
        for plane, chip in recording["chips"].items()
    }}


SCOPED = _expanded(json.loads((HERE / "recorded_trace_scoped.json").read_text()))
UNSCOPED_RECORDING = json.loads((HERE / "recorded_trace.json").read_text())
MIXTRAL = json.loads((BENCH / "configs/mixtral-8x7b-int8-1chip.json").read_text())
MISTRAL = json.loads((BENCH / "configs/mistral-7b-int8.json").read_text())
READERS = M.load_readers()
SCOPE_METRICS = ("attention_dev_pct", "page_gather_dev_pct", "dense_mlp_dev_pct",
                 "expert_scan_dev_pct", "expert_scan_roofline")


# -- the wire format -------------------------------------------------------------


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def xspace(planes):
    """A serialized XSpace. planes: [(name, {stat id: name}, [(op name,
    [(stat id, "str" | "ref", value)])], {line name: [(metadata id, start_ns,
    duration_ns)]})]; metadata ids count from 1 in the order of the ops."""
    space = b""
    for name, stat_names, ops, lines in planes:
        plane = _field(1, 7) + _field(2, name)
        for lid, (line_name, events) in enumerate(lines.items(), start=1):
            line = _field(1, lid) + _field(2, line_name) + _field(3, 0)
            for mid, start_ns, dur_ns in events:
                line += _field(4, _field(1, mid) + _field(2, start_ns * 1000)
                               + _field(3, dur_ns * 1000))
            plane += _field(3, line)
        for sid, sname in stat_names.items():
            plane += _field(5, _entry(sid, _field(1, sid) + _field(2, sname)))
        for mid, (op_name, stats) in enumerate(ops, start=1):
            meta = _field(1, mid) + _field(2, op_name)
            for sid, kind, value in stats:
                meta += _field(5, _field(1, sid) + _field(5 if kind == "str" else 7, value))
            plane += _field(4, _entry(mid, meta))
        space += _field(1, plane)
    return space


STATS = {1: "flops", 2: "tf_op", 300: "jit(decode)/while/body/mtpu.dense_mlp/dot_general:"}
OPS = [
    ("jit__decode_block_fn(77)", []),
    ("%fusion.1 = bf16[16,4096]{1,0} fusion(%p0), kind=kLoop", [
        (1, "ref", 300),  # not the tf_op stat: ignored
        (2, "str", "jit(decode)/mtpu.attention/mtpu.page_gather/gather:"),
    ]),
    ("%fusion.2 = f32[16,14336]{1,0} fusion(%p1), kind=kOutput", [(2, "ref", 300)]),
    ("%copy.3 = bf16[8]{0} copy(%p2)", [(2, "str", "jit(decode)/transpose")]),
    ("%while.4 = (s32[]) while(%t), body=%b", [(2, "str", "jit(decode)/mtpu.attention/while")]),
]
LINES = {
    "XLA Modules": [(1, 0, 10_000)],
    "XLA Ops": [(5, 0, 9_000), (2, 0, 2_000), (3, 2_000, 5_000), (3, 7_000, 1_000),
                (4, 8_000, 1_000)],
}


def test_an_operations_scope_is_read_from_the_bytes_of_its_metadata():
    data = xspace([
        ("/device:TPU:0", STATS, OPS, LINES),
        ("/host:CPU", {2: "tf_op"}, [("python", [(2, "str", "mtpu.x")])], {}),
    ])
    got = T.op_scopes(data)
    assert set(got) == {"/device:TPU:0"}
    tf_ops = got["/device:TPU:0"]
    assert tf_ops[OPS[1][0]] == "jit(decode)/mtpu.attention/mtpu.page_gather/gather:"
    assert tf_ops[OPS[2][0]] == STATS[300]  # a ref_value names a stat's metadata
    assert OPS[0][0] not in tf_ops
    # the innermost scope names the part; what has none is kept apart
    assert T.scope_of(tf_ops[OPS[1][0]]) == "mtpu.page_gather"
    assert T.scope_of("jit(f)/transpose") == T.UNSCOPED


def test_load_xplane_gives_every_operation_its_scope(tmp_path):
    path = tmp_path / "plugins/profile/2026_01_01/t.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(xspace([("/device:TPU:0", STATS, OPS, LINES)]))
    # as the serving container reduces a traced run's directory, with a sample to record
    whole = T.reduce_dir(str(tmp_path), sample_seconds=8.5e-6)
    kept = whole["sample"]["chips"]["/device:TPU:0"]
    assert len(kept["ops"]) == 3 and kept["ops"][0][3] == "mtpu.page_gather"
    assert kept["modules"] == []  # the program's event ends after the cut
    loaded = T.load_xplane(str(path))
    chip = loaded["chips"]["/device:TPU:0"]
    assert [m[0] for m in chip["modules"]] == ["jit__decode_block_fn(77)"]
    # the while only contains others: not time of its own
    assert [(n.split("_")[0], s) for n, _t, _d, s in chip["ops"]] == [
        ("fusion.1", "mtpu.page_gather"), ("fusion.2", "mtpu.dense_mlp"),
        ("fusion.2", "mtpu.dense_mlp"), ("copy.3", T.UNSCOPED),
    ]
    got = T.reduce_events(loaded)
    assert got["scopes"] == {
        "mtpu.page_gather": {"time_s": pytest.approx(2e-6), "count": 1},
        "mtpu.dense_mlp": {"time_s": pytest.approx(6e-6), "count": 2},
        T.UNSCOPED: {"time_s": pytest.approx(1e-6), "count": 1},
    }
    assert sum(r["time_s"] for r in got["scopes"].values()) == pytest.approx(got["busy_s"])
    assert got["program_scopes"] == {"jit__decode_block_fn": got["scopes"]}


def test_a_plane_whose_metadata_names_no_scope_gives_no_table(tmp_path):
    bare = [(name, []) for name, _stats in OPS]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xspace([("/device:TPU:0", {}, bare, LINES)]))
    loaded = T.load_xplane(str(path))
    assert all(scope is None for *_e, scope in loaded["chips"]["/device:TPU:0"]["ops"])
    assert T.reduce_events(loaded)["scopes"] is None


# -- the reduction ---------------------------------------------------------------


def _check_tables(got, ops, tolerance):
    """Scopes plus the unscoped remainder are the operations' time, which is
    the busy time but for operations that overlap; a program's scopes are
    its operations' time, which lies inside its module events."""
    total = sum(row["time_s"] for row in got["scopes"].values())
    assert total == pytest.approx(sum(d for _n, _s, d, *_ in ops))
    assert total == pytest.approx(got["busy_s"], rel=tolerance)
    assert sum(row["count"] for row in got["scopes"].values()) == len(ops)
    by_program = {
        p: sum(row["time_s"] for row in rows.values()) for p, rows in got["program_scopes"].items()
    }
    assert sum(by_program.values()) == pytest.approx(total)
    for program, seconds in by_program.items():
        if program != T.NO_PROGRAM:
            inside = got["programs"][program]["time_s"]
            assert seconds <= inside * (1 + 1e-9)
            # (a helper program of a few microseconds is mostly the gaps between its operations)
            assert seconds == pytest.approx(inside, rel=tolerance, abs=5e-6)
    for scope in got["scopes"]:
        assert scope == T.UNSCOPED or scope.startswith("mtpu.")


def test_scopes_of_a_hand_made_trace():
    chip = {
        "modules": [("jit_a(1)", 0.0, 1.0), ("jit_b(2)", 2.0, 1.0)],
        "ops": [("x", 0.0, 0.5, "mtpu.attention"), ("y", 0.5, 0.5, T.UNSCOPED),
                ("x", 2.0, 0.75, "mtpu.attention"), ("z", 2.75, 0.25, "mtpu.dense_mlp")],
    }
    got = T.reduce_events({"chips": {"/device:TPU:0": chip}, "lines": {}})
    assert got["scopes"]["mtpu.attention"] == {"time_s": pytest.approx(1.25), "count": 2}
    assert got["program_scopes"]["jit_b"] == {
        "mtpu.attention": {"time_s": pytest.approx(0.75), "count": 1},
        "mtpu.dense_mlp": {"time_s": pytest.approx(0.25), "count": 1},
    }
    _check_tables(got, chip["ops"], 1e-9)
    # the ranking and the gaps are as they were: the ledger's breakdown reads them
    assert got["device_ops"][0] == ["x", pytest.approx(1.25)]
    assert got["idle_gaps"] == [["jit_a_-_jit_b", pytest.approx(1.0)]]


def test_scopes_of_the_trace_recorded_on_the_chip():
    ops = SCOPED["chips"]["/device:TPU:0"]["ops"]
    got = T.reduce_events(SCOPED)
    assert {"mtpu.attention", "mtpu.page_gather", "mtpu.dense_mlp"} <= set(got["scopes"])
    _check_tables(got, ops, 0.01)
    assert len(got["device_ops"]) == 10 and len(got["idle_gaps"]) <= 5


def test_two_chips_are_averaged():
    chip = SCOPED["chips"]["/device:TPU:0"]
    one = T.reduce_events(SCOPED)
    two = T.reduce_events({"chips": {"/device:TPU:0": chip, "/device:TPU:1": chip}, "lines": {}})
    assert two["scopes"].keys() == one["scopes"].keys()
    for scope, row in one["scopes"].items():
        assert two["scopes"][scope]["time_s"] == pytest.approx(row["time_s"])
        assert two["scopes"][scope]["count"] == pytest.approx(row["count"])


# -- the readers -------------------------------------------------------------------


def _run(trace, config=MISTRAL, log=None):
    return RunData(
        cell={}, config=config, mix={"loop": "closed"},
        times={"window_open": 100.0, "window_close": 151.0},
        outcomes=[], scored=[], kv_pages_peak=None, engine_log=log or {},
        counters_open={"mtpu_decode_steps_total": [({}, 0.0)],
                       "mtpu_generated_tokens_total": [({}, 0.0)],
                       "mtpu_ttft_seconds_count": [({}, 0.0)]},
        counters_close={"mtpu_decode_steps_total": [({}, 400.0)],
                        "mtpu_generated_tokens_total": [({}, 4840.0)],
                        "mtpu_ttft_seconds_count": [({}, 40.0)]},
        device={"kind": "TPU v5 lite", "decode_block": 8}, trace=trace,
    )


@pytest.mark.parametrize("quantity", SCOPE_METRICS)
def test_a_trace_without_metadata_reads_none_never_zero(quantity):
    old = T.reduce_events(UNSCOPED_RECORDING)
    assert old["scopes"] is None
    assert READERS[quantity](_run(old, MIXTRAL)) is None
    assert READERS[quantity](_run(None, MIXTRAL)) is None  # an untraced run


def test_shares_are_of_the_operations_time_and_an_absent_scope_is_none():
    got = T.reduce_events(SCOPED)
    run = _run(got)
    shares = {q: READERS[q](run) for q in SCOPE_METRICS[:4]}
    assert shares["expert_scan_dev_pct"] is None  # a dense model's trace has no such scope
    seen = [v for v in shares.values() if v is not None]
    assert len(seen) == 3 and all(0 < v < 100 for v in seen)
    rest = sum(
        row["time_s"] for scope, row in got["scopes"].items()
        if scope not in ("mtpu.attention", "mtpu.page_gather", "mtpu.dense_mlp")
    )
    total = sum(row["time_s"] for row in got["scopes"].values())
    assert sum(seen) == pytest.approx(100.0 * (1 - rest / total))


def test_the_expert_layers_roofline_by_hand():
    """One chunk of 2048 tokens a call on the 7-layer Mixtral: 2 of 8 experts
    a token, every expert's weights once a call; decode steps of 12 reach
    8 x (1 - 0.75**12) experts and are bound by their bytes."""
    llama = M.load_family(MIXTRAL)
    expert = 3 * 4096 * 14336
    chunk = llama.expert_scan(MIXTRAL, 2048, 1)
    assert chunk["flops"] == 2 * expert * 2 * 2048 * 7 == pytest.approx(1.0102e13, rel=1e-4)
    assert chunk["bytes"] == pytest.approx(7 * expert * 8 * (1 - 0.75**2048) + 7 * 2048 * 4096 * 4)
    step = llama.expert_scan(MIXTRAL, 12, 1)
    assert step["bytes"] == pytest.approx(7 * expert * 8 * (1 - 0.75**12) + 7 * 12 * 4096 * 4)
    assert llama.expert_scan(MISTRAL, 2048, 1) is None  # no such layer
    # a traced 6 s of a 51 s window: 3 s under the scope, 6 prefill calls and
    # 10 decode blocks of 8 steps at a mean batch of 12
    trace = {
        "window_s": 6.0, "busy_s": 5.5,
        "scopes": {"mtpu.expert_scan": {"time_s": 3.0, "count": 900},
                   "mtpu.attention": {"time_s": 2.0, "count": 500}},
        "programs": {"jit_prefill_chunk_off0": {"time_s": 2.0, "count": 6},
                     "jit__decode_block_fn": {"time_s": 3.0, "count": 10}},
    }
    log = {i: {"n_prompt": 2448, "first_token_at": 101.0 + i} for i in range(25)}
    run = _run(trace, MIXTRAL, log)
    assert run.decode_batch_mean() == pytest.approx(12.0)
    scale = 51.0 / 6.0
    prefill = llama.expert_scan(MIXTRAL, 25 * 2448, 6 * scale)
    decode = llama.expert_scan(MIXTRAL, 12 * 80 * scale, 80 * scale)
    least = prefill["flops"] / 197e12 + decode["bytes"] / 819e9
    assert READERS["expert_scan_roofline"](run) == pytest.approx(100 * least / (3.0 * scale))
    assert READERS["expert_scan_dev_pct"](run) == pytest.approx(60.0)
    assert READERS["expert_scan_roofline"](_run(trace, MISTRAL, log)) is None
    trace["scopes"]["mtpu.expert_scan"]["time_s"] = 0.01  # faster than the chip allows
    with pytest.raises(AssertionError):
        READERS["expert_scan_roofline"](run)
