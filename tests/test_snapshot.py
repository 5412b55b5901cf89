"""Memory-snapshot subsystem tests (modal_examples_tpu/snapshot/): store,
codec, capture/restore policy, FunctionSpec plumbing, the autoscaler's
first-warm-boot gate, prometheus accounting, and end-to-end second-boot
restores against real container worker processes — including the
examples/06_gpu_and_ml/tpu_snapshot.py Embedder (the gpu_snapshot.py analog
in BASELINE.json)."""

import collections
import json
import os
import threading
import types

import pytest

import modal_examples_tpu as mtpu
from modal_examples_tpu.core.app import load_module_from_path
from modal_examples_tpu.core.executor import FunctionPool
from modal_examples_tpu.snapshot import build_and_enter, codec
from modal_examples_tpu.snapshot.store import (
    SnapshotStore,
    compute_snapshot_key,
    default_root,
    source_hash_for,
)
from modal_examples_tpu.utils.metrics import (
    SNAPSHOT_BOOTS_METRIC,
    SNAPSHOT_CAPTURES_METRIC,
    record_snapshot_boot,
)
from modal_examples_tpu.utils.prometheus import Registry, default_registry


@pytest.fixture()
def store(tmp_path):
    return SnapshotStore(root=tmp_path / "snaps")


# --------------------------------------------------------------------------
# Store
# --------------------------------------------------------------------------


class TestStore:
    def test_roundtrip(self, store):
        assert not store.has("k1")
        assert store.put("k1", b"payload", {"tag": "t"})
        assert store.has("k1")
        payload, meta = store.get("k1")
        assert payload == b"payload"
        assert meta["manifest"]["tag"] == "t"
        assert meta["size_bytes"] == 7

    def test_miss(self, store):
        assert store.get("nope") is None
        assert store.inspect("nope") is None

    def test_corrupt_payload_is_deleted(self, store):
        store.put("k1", b"payload")
        store._state_path("k1").write_bytes(b"garbage")
        assert store.get("k1") is None  # checksum mismatch
        assert not store.has("k1")  # corrupt entry removed

    def test_missing_payload_is_deleted(self, store):
        store.put("k1", b"payload")
        store._state_path("k1").unlink()
        assert store.get("k1") is None
        assert not store.has("k1")

    def test_corrupt_meta_reads_as_miss_and_self_heals(self, store):
        store.put("k1", b"payload")
        store._meta_path("k1").write_text("{not json")
        assert not store.has("k1")  # parse-based: dead entry never reads live
        assert store.get("k1") is None
        assert not store._entry_dir("k1").exists()  # corrupt dir removed

    def test_put_replaces_corrupt_entry(self, store):
        store.put("k1", b"old")
        store._meta_path("k1").write_text("{not json")
        assert store.put("k1", b"new")  # rename onto corrupt dir: replace it
        payload, _ = store.get("k1")
        assert payload == b"new"

    def test_clear_removes_corrupt_entries(self, store):
        store.put("k1", b"x")
        store._meta_path("k1").write_text("{not json")
        assert store.clear() == 1
        assert not store._entry_dir("k1").exists()

    def test_malformed_env_knobs_fall_back_to_defaults(self, monkeypatch, tmp_path):
        from modal_examples_tpu.snapshot.store import DEFAULT_MAX_ENTRIES

        monkeypatch.setenv("MTPU_SNAPSHOT_MAX_ENTRIES", "lots")
        monkeypatch.setenv("MTPU_SNAPSHOT_MAX_BYTES", "1g")
        s = SnapshotStore(root=tmp_path)  # must not raise inside a boot path
        assert s.max_entries == DEFAULT_MAX_ENTRIES
        assert s.max_bytes is None

    def test_delete_and_clear(self, store):
        store.put("a", b"1")
        store.put("b", b"2")
        assert store.delete("a")
        assert not store.delete("a")
        assert store.clear() == 1
        assert store.entries() == []

    def test_lru_eviction_by_count(self, tmp_path):
        store = SnapshotStore(root=tmp_path, max_entries=2)
        store.put("a", b"1")
        store.put("b", b"2")
        store.get("a")  # a is now most recently used
        store.put("c", b"3")  # evicts b (least recently used)
        keys = {e["key"] for e in store.entries()}
        assert keys == {"a", "c"}

    def test_eviction_by_bytes(self, tmp_path):
        store = SnapshotStore(root=tmp_path, max_entries=100, max_bytes=10)
        store.put("a", b"x" * 8)
        store.put("b", b"y" * 8)  # total 16 > 10: oldest goes
        keys = {e["key"] for e in store.entries()}
        assert keys == {"b"}

    def test_first_writer_wins(self, store):
        store.put("k", b"first", {"tag": "one"})
        store.put("k", b"second", {"tag": "two"})
        payload, _ = store.get("k")
        assert payload == b"first"  # os.rename onto an existing dir fails

    def test_default_root_env_override(self, monkeypatch, tmp_path):
        monkeypatch.setenv("MTPU_SNAPSHOT_DIR", str(tmp_path / "custom"))
        assert default_root() == tmp_path / "custom"

    def test_from_volume_shares_across_replicas(self, tmp_path):
        vol = types.SimpleNamespace(local_path=tmp_path / "vol")
        s1 = SnapshotStore.from_volume(vol)
        s1.put("k", b"shared")
        s2 = SnapshotStore.from_volume(vol)
        payload, _ = s2.get("k")
        assert payload == b"shared"


class TestKey:
    BASE = dict(
        image_digest="img1", source_hash="src1", env={"A": "1"}, cls_params=b"p"
    )

    def test_deterministic(self):
        k1 = compute_snapshot_key(host_arch="mt", **self.BASE)
        k2 = compute_snapshot_key(host_arch="mt", **self.BASE)
        assert k1 == k2

    @pytest.mark.parametrize(
        "field,value",
        [
            ("image_digest", "img2"),
            ("source_hash", "src2"),
            ("env", {"A": "2"}),
            ("cls_params", b"q"),
        ],
    )
    def test_every_component_changes_key(self, field, value):
        base = compute_snapshot_key(host_arch="mt", **self.BASE)
        changed = compute_snapshot_key(
            host_arch="mt", **{**self.BASE, field: value}
        )
        assert base != changed

    def test_host_arch_prefix(self):
        key = compute_snapshot_key(host_arch="cafe1234", **self.BASE)
        assert key.startswith("cafe1234-")

    def test_source_hash_tracks_code(self):
        class A:
            def f(self):
                return 1

        class B:
            def f(self):
                return 2

        assert source_hash_for(A) != source_hash_for(B)
        assert source_hash_for(A) == source_hash_for(A)

    def test_source_hash_falls_back_to_fn_bytes(self):
        cls = types.new_class("Synthetic")  # no retrievable source
        assert source_hash_for(cls, b"bytes1") != source_hash_for(cls, b"bytes2")


# --------------------------------------------------------------------------
# Codec
# --------------------------------------------------------------------------

Point = collections.namedtuple("Point", "x y")


class TestCodec:
    def test_plain_roundtrip(self):
        state = {"a": 1, "b": "two", "c": [1, 2, {"d": (3, 4)}]}
        payload, rebuild = codec.encode_state(state)
        assert rebuild == []
        assert codec.decode_state(payload) == state

    def test_namedtuple_roundtrip(self):
        payload, rebuild = codec.encode_state({"p": Point(1, 2)})
        assert rebuild == []
        out = codec.decode_state(payload)
        assert out["p"] == Point(1, 2)
        assert isinstance(out["p"], Point)

    def test_jax_array_roundtrip(self):
        import jax.numpy as jnp
        import numpy as np

        arr = jnp.arange(6.0).reshape(2, 3)
        params = {"layer": {"w": arr, "b": jnp.ones(3)}}
        payload, rebuild = codec.encode_state({"params": params})
        assert rebuild == []
        out = codec.decode_state(payload)["params"]
        assert np.allclose(np.asarray(out["layer"]["w"]), np.asarray(arr))
        # decoded leaves are device arrays again, not numpy
        assert type(out["layer"]["w"]).__module__.startswith(("jax", "jaxlib"))

    def test_unpicklable_becomes_rebuild_marker(self):
        payload, rebuild = codec.encode_state(
            {"ok": 1, "lock": threading.Lock(), "gen": (x for x in range(3))}
        )
        assert sorted(rebuild) == ["gen", "lock"]
        assert codec.decode_state(payload) == {"ok": 1}

    def test_jitted_callable_roundtrips_or_is_marker(self):
        # jax versions differ: when cloudpickle can ship the jit wrapper it
        # round-trips (re-jitting lazily on first call — a compile-cache disk
        # hit); otherwise it must surface as a rebuild marker, never an error
        import jax

        payload, rebuild = codec.encode_state({"fn": jax.jit(lambda x: x + 1)})
        if rebuild:
            assert rebuild == ["fn"]
        else:
            out = codec.decode_state(payload)
            assert int(out["fn"](1)) == 2

    def test_encode_attr_raises_codec_error(self):
        with pytest.raises(codec.CodecError):
            codec.encode_attr(threading.Lock())


# --------------------------------------------------------------------------
# build_and_enter policy (in-process)
# --------------------------------------------------------------------------

_hook_calls = {"snap": 0, "plain": 0}


class Model:
    def snap_load(self):
        _hook_calls["snap"] += 1
        self.weights = {"w": [1.0, 2.0]}

    def plain_enter(self):
        _hook_calls["plain"] += 1
        self.client = object()  # per-boot, never snapshotted

    def exit_hook(self):
        pass


META = {
    "enter": ["snap_load", "plain_enter"],
    "exit": ["exit_hook"],
    "snap_enter": ["snap_load"],
}


@pytest.fixture(autouse=True)
def _reset_hook_calls():
    _hook_calls["snap"] = _hook_calls["plain"] = 0


class TestBuildAndEnter:
    def boot(self, tmp_path, key="key-1", meta=META, cls=Model, params=None):
        return build_and_enter(
            cls,
            params or {},
            meta,
            snapshot_key=key,
            snapshot_dir=str(tmp_path / "snaps"),
            tag="t.Model",
        )

    def test_miss_then_hit_skips_snap_hook(self, tmp_path):
        obj1, info1 = self.boot(tmp_path)
        assert info1 == {"snapshot": "miss", "captured": True}
        assert _hook_calls == {"snap": 1, "plain": 1}

        obj2, info2 = self.boot(tmp_path)
        assert info2["snapshot"] == "hit"
        assert info2["skipped_hooks"] == ["snap_load"]
        # the snap hook body did NOT re-execute; the plain hook ran again
        assert _hook_calls == {"snap": 1, "plain": 2}
        assert obj2.weights == {"w": [1.0, 2.0]}
        assert hasattr(obj2, "client")

    def test_no_key_means_off(self, tmp_path):
        _obj, info = build_and_enter(Model, {}, META, snapshot_key=None)
        assert info == {"snapshot": "off"}
        assert _hook_calls == {"snap": 1, "plain": 1}

    def test_kill_switch_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MTPU_SNAPSHOT", "0")
        _obj, info = self.boot(tmp_path)
        assert info == {"snapshot": "off"}
        store = SnapshotStore(root=tmp_path / "snaps")
        assert store.entries() == []

    def test_corrupted_entry_falls_back_to_cold_boot(self, tmp_path):
        self.boot(tmp_path)
        store = SnapshotStore(root=tmp_path / "snaps")
        store._state_path("key-1").write_bytes(b"garbage")
        obj, info = self.boot(tmp_path)
        assert info["snapshot"] == "fallback"
        assert info["captured"]  # re-captured for the next boot
        assert _hook_calls["snap"] == 2
        assert obj.weights == {"w": [1.0, 2.0]}
        _obj, info3 = self.boot(tmp_path)
        assert info3["snapshot"] == "hit"

    def test_lifecycle_shape_change_falls_back(self, tmp_path):
        self.boot(tmp_path)

        class Model2(Model):
            def extra_snap(self):
                self.extra = True

        meta2 = {
            "enter": ["snap_load", "extra_snap", "plain_enter"],
            "exit": [],
            "snap_enter": ["snap_load", "extra_snap"],
        }
        # same key (stale), different snap-hook set: restore must refuse
        _obj, info = self.boot(tmp_path, meta=meta2, cls=Model2)
        assert info["snapshot"] == "fallback"

    def test_unpicklable_snap_attr_reruns_owning_hook(self, tmp_path):
        calls = {"n": 0}

        class Jitty:
            def load(self):
                calls["n"] += 1
                self.weights = [1.0]
                self.compiled = threading.Lock()  # stands in for jax.jit

        meta = {"enter": ["load"], "exit": [], "snap_enter": ["load"]}
        _obj, info1 = self.boot(tmp_path, meta=meta, cls=Jitty)
        assert info1["captured"]
        obj2, info2 = self.boot(tmp_path, meta=meta, cls=Jitty)
        # still a hit, but the hook owning the rebuild marker re-runs
        assert info2["snapshot"] == "hit"
        assert info2["rerun_hooks"] == ["load"]
        assert calls["n"] == 2
        assert isinstance(obj2.compiled, type(threading.Lock()))

    def test_mutated_baseline_attr_reruns_owning_hook(self, tmp_path):
        calls = {"n": 0}

        class Placeholder:
            def __init__(self):
                self.client = None  # rebound to an unpicklable by the hook

            def load(self):
                calls["n"] += 1
                self.weights = [1.0]
                self.client = threading.Lock()

        meta = {"enter": ["load"], "exit": [], "snap_enter": ["load"]}
        _obj, info1 = self.boot(tmp_path, meta=meta, cls=Placeholder)
        assert info1["captured"]
        obj2, info2 = self.boot(tmp_path, meta=meta, cls=Placeholder)
        # the restored boot must NOT serve the __init__ placeholder: the
        # hook that rebound `client` re-runs
        assert info2["snapshot"] == "hit"
        assert info2["rerun_hooks"] == ["load"]
        assert calls["n"] == 2
        assert obj2.client is not None
        assert obj2.weights == [1.0]

    def test_hit_failure_after_non_snap_side_effects_raises(self, tmp_path):
        effects = []
        flag = tmp_path / "explode"

        class Sideful:
            def load(self):
                self.w = [1.0]

            def effect(self):
                effects.append("ran")  # external side effect (e.g. commit)

            def boom(self):
                if flag.exists():
                    raise RuntimeError("transient failure after side effects")

        meta = {
            "enter": ["load", "effect", "boom"],
            "exit": [],
            "snap_enter": ["load"],
        }
        self.boot(tmp_path, meta=meta, cls=Sideful)
        assert effects == ["ran"]
        flag.touch()
        # on the restored boot, `effect` completes before `boom` raises: a
        # silent cold rerun would double `effect` — the boot must fail like
        # a cold boot whose hook raised (and drop the entry for next time)
        with pytest.raises(RuntimeError, match="transient"):
            self.boot(tmp_path, meta=meta, cls=Sideful)
        assert effects == ["ran", "ran"]  # not tripled by a hidden cold rerun
        assert not SnapshotStore(root=tmp_path / "snaps").has("key-1")

    def test_poison_snapshot_is_deleted_and_boot_goes_cold(self, tmp_path):
        class Fragile:
            def load(self):
                self.mode = getattr(self, "mode", "good")

            def check(self):
                assert self.mode == "good"

        meta = {"enter": ["load", "check"], "exit": [], "snap_enter": ["load"]}
        self.boot(tmp_path, meta=meta, cls=Fragile)
        # poison the stored state: restored attr makes a later hook raise
        store = SnapshotStore(root=tmp_path / "snaps")
        payload, _ = store.get("key-1")
        bad, _ = codec.encode_state({"mode": "poison"})
        store.delete("key-1")
        store.put("key-1", bad, {"hook_attrs": {"load": ["mode"]}, "rebuild": []})
        obj, info = self.boot(tmp_path, meta=meta, cls=Fragile)
        # the boot survived, state is cold-boot-correct, entry was replaced
        assert obj.mode == "good"
        assert info["captured"]

    def test_params_applied_before_hooks(self, tmp_path):
        class P:
            def load(self):
                self.doubled = self.base * 2

        meta = {"enter": ["load"], "exit": [], "snap_enter": ["load"]}
        obj, _ = self.boot(tmp_path, meta=meta, cls=P, params={"base": 21})
        assert obj.doubled == 42


# --------------------------------------------------------------------------
# FunctionSpec / ContainerConfig plumbing (the silently-dropped-kwarg bugfix)
# --------------------------------------------------------------------------


class TestSpecPlumbing:
    def test_function_kwarg_reaches_spec(self):
        app = mtpu.App("snap-plumb-fn")

        @app.function(enable_memory_snapshot=True, serialized=True,
                      experimental_options={"x": 1})
        def f():
            return 1

        assert f.spec.enable_memory_snapshot is True
        assert f.spec.serialized is True
        assert f.spec.experimental_options == {"x": 1}

    def test_cls_kwarg_reaches_spec(self):
        app = mtpu.App("snap-plumb-cls")

        @app.cls(enable_memory_snapshot=True, experimental_options={"y": 2})
        class C:
            @mtpu.method()
            def m(self):
                return 1

        assert C._spec.enable_memory_snapshot is True
        assert C._spec.experimental_options == {"y": 2}

    def test_default_is_off(self):
        app = mtpu.App("snap-plumb-default")

        @app.cls()
        class C:
            @mtpu.method()
            def m(self):
                return 1

        assert C._spec.enable_memory_snapshot is False
        assert C._spec.container_config().snapshot_key is None

    def test_cls_container_config_resolves_key(self, monkeypatch, tmp_path):
        monkeypatch.setenv("MTPU_SNAPSHOT_DIR", str(tmp_path))
        app = mtpu.App("snap-plumb-key")

        @app.cls(enable_memory_snapshot=True)
        class C:
            @mtpu.enter(snap=True)
            def load(self):
                self.ready = True

            @mtpu.method()
            def m(self):
                return 1

        cfg = C._spec.container_config()
        assert cfg.snapshot_key is not None
        assert cfg.snapshot_dir == str(tmp_path)
        # key is stable across recomputation (supervisor/container agreement)
        assert C._spec.container_config().snapshot_key == cfg.snapshot_key

    def test_plain_function_gets_no_key(self):
        app = mtpu.App("snap-plumb-plainfn")

        @app.function(enable_memory_snapshot=True)
        def f():
            return 1

        # snapshots only apply to Cls lifecycles (no @enter hooks on plain fns)
        assert f.spec.container_config().snapshot_key is None

    def test_snap_enter_meta_collected(self):
        class C:
            @mtpu.enter(snap=True)
            def a(self):
                pass

            @mtpu.enter()
            def b(self):
                pass

        from modal_examples_tpu.core.cls import _collect_lifecycle

        meta = _collect_lifecycle(C)
        assert meta["snap_enter"] == ["a"]
        assert meta["enter"][0] == "a"  # snap hooks ordered first


# --------------------------------------------------------------------------
# Autoscaler first-warm-boot gate
# --------------------------------------------------------------------------


class TestSnapshotGate:
    def _fake_pool(self, tmp_path, key="gate-key"):
        cfg = types.SimpleNamespace(snapshot_key=key, snapshot_dir=str(tmp_path))
        return types.SimpleNamespace(
            _snapshot_gate=bool(key), container_config=cfg, containers=[]
        )

    def test_gate_holds_until_entry_or_warm_boot(self, tmp_path):
        pool = self._fake_pool(tmp_path)
        assert FunctionPool._snapshot_pending_first_capture(pool)
        assert FunctionPool._snapshot_pending_first_capture(pool)  # still held

    def test_gate_opens_when_store_has_entry(self, tmp_path):
        pool = self._fake_pool(tmp_path)
        SnapshotStore(root=tmp_path).put("gate-key", b"x")
        assert not FunctionPool._snapshot_pending_first_capture(pool)
        assert not pool._snapshot_gate  # open for good

    def test_gate_opens_after_first_warm_boot_without_capture(self, tmp_path):
        pool = self._fake_pool(tmp_path)
        pool.containers = [types.SimpleNamespace(ever_ready=True)]
        assert not FunctionPool._snapshot_pending_first_capture(pool)
        assert not pool._snapshot_gate

    def test_no_key_no_gate(self, tmp_path):
        pool = self._fake_pool(tmp_path, key=None)
        assert not FunctionPool._snapshot_pending_first_capture(pool)


# --------------------------------------------------------------------------
# Metrics
# --------------------------------------------------------------------------


class TestMetrics:
    def test_record_and_expose(self):
        reg = Registry()
        record_snapshot_boot("a.M", "miss", captured=True, registry=reg)
        record_snapshot_boot("a.M", "hit", registry=reg)
        record_snapshot_boot("a.M", "hit", registry=reg)
        assert reg.value(SNAPSHOT_BOOTS_METRIC, {"function": "a.M", "result": "hit"}) == 2
        assert reg.value(SNAPSHOT_BOOTS_METRIC, {"function": "a.M", "result": "miss"}) == 1
        assert reg.value(SNAPSHOT_CAPTURES_METRIC, {"function": "a.M"}) == 1
        text = reg.expose()
        assert "mtpu_snapshot_boots_total" in text
        assert 'result="hit"' in text
        assert "# TYPE mtpu_snapshot_boots_total counter" in text

    def test_unwritten_series_reads_zero(self):
        reg = Registry()
        assert reg.value(SNAPSHOT_BOOTS_METRIC, {"function": "x", "result": "hit"}) == 0.0


# --------------------------------------------------------------------------
# End-to-end: process backend, second boot restores
# --------------------------------------------------------------------------

e2e_app = mtpu.App("snapshot-e2e")


@e2e_app.cls(timeout=60, enable_memory_snapshot=True)
class SnapService:
    counter_file: str = mtpu.parameter(default="")

    @mtpu.enter(snap=True)
    def load(self):
        # side-effect counter shared across container processes
        with open(self.counter_file, "a") as f:
            f.write("x")
        self.weights = {"w": [3.0, 4.0]}

    @mtpu.method()
    def norm(self) -> float:
        w = self.weights["w"]
        return (w[0] ** 2 + w[1] ** 2) ** 0.5

    @mtpu.method()
    def boots(self) -> int:
        return os.path.getsize(self.counter_file)


def _boot_counts(tag):
    return {
        r: default_registry.value(
            SNAPSHOT_BOOTS_METRIC, {"function": tag, "result": r}
        )
        for r in ("hit", "miss", "fallback")
    }


class TestEndToEnd:
    def test_second_container_boot_restores(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MTPU_SNAPSHOT_DIR", str(tmp_path / "snaps"))
        counter = tmp_path / "enter-count"
        counter.touch()
        tag = "snapshot-e2e.SnapService"
        before = _boot_counts(tag)

        with e2e_app.run():
            svc = SnapService(counter_file=str(counter))
            assert svc.norm.remote() == 5.0
        assert counter.read_text() == "x"  # first boot ran the hook

        with e2e_app.run():
            svc = SnapService(counter_file=str(counter))
            assert svc.norm.remote() == 5.0  # restored state serves correctly
            assert svc.boots.remote() == 1
        # the snap hook body never re-executed in the second container
        assert counter.read_text() == "x"

        after = _boot_counts(tag)
        assert after["miss"] == before["miss"] + 1
        assert after["hit"] == before["hit"] + 1
        # hit/miss visible in the prometheus exposition
        assert "mtpu_snapshot_boots_total" in default_registry.expose()

        # one entry in the store, inspectable, attributed to this service
        store = SnapshotStore(root=tmp_path / "snaps")
        entries = store.entries()
        assert len(entries) == 1
        assert entries[0]["manifest"]["tag"] == tag
        assert entries[0]["manifest"]["hook_attrs"] == {"load": ["weights"]}

    def test_corrupt_store_still_boots(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MTPU_SNAPSHOT_DIR", str(tmp_path / "snaps"))
        counter = tmp_path / "enter-count"
        counter.touch()
        tag = "snapshot-e2e.SnapService"

        with e2e_app.run():
            svc = SnapService(counter_file=str(counter))
            assert svc.norm.remote() == 5.0

        store = SnapshotStore(root=tmp_path / "snaps")
        [entry] = store.entries()
        store._state_path(entry["key"]).write_bytes(b"garbage")
        before = _boot_counts(tag)

        with e2e_app.run():
            svc = SnapService(counter_file=str(counter))
            assert svc.norm.remote() == 5.0  # fallback boot, no error
        assert counter.read_text() == "xx"  # hook re-ran on the cold fallback
        after = _boot_counts(tag)
        assert after["fallback"] == before["fallback"] + 1


# --------------------------------------------------------------------------
# Example smoke: the tpu_snapshot.py Embedder, end-to-end, twice
# --------------------------------------------------------------------------


class TestExampleSmoke:
    def test_embedder_second_boot_is_snapshot_hit(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MTPU_SNAPSHOT_DIR", str(tmp_path / "snaps"))
        from modal_examples_tpu.utils.docs import repo_root

        module = load_module_from_path(
            str(repo_root() / "examples/06_gpu_and_ml/tpu_snapshot.py")
        )
        tag = "example-tpu-snapshot.Embedder"
        before = _boot_counts(tag)

        with module.app.run():
            r1 = module.Embedder().embed.remote(["first boot"])
        mid = _boot_counts(tag)
        assert mid["miss"] == before["miss"] + 1

        with module.app.run():
            r2 = module.Embedder().embed.remote(["second boot"])
        after = _boot_counts(tag)
        assert after["hit"] == mid["hit"] + 1
        assert r1["dim"] == r2["dim"] > 0

        # the captured entry holds the pure-state hook only; the jit warmup
        # hook is per-boot by design (unpicklable executables)
        store = SnapshotStore(root=tmp_path / "snaps")
        [entry] = store.entries()
        assert entry["manifest"]["hook_attrs"] == {"load": ["cfg", "params"]}
        assert entry["manifest"]["rebuild"] == []


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------


class TestCli:
    def test_list_inspect_clear(self, tmp_path, capsys):
        from modal_examples_tpu.core.cli import cmd_snapshot

        store = SnapshotStore(root=tmp_path)
        store.put("key-a", b"123", {"tag": "app.M"})

        assert cmd_snapshot(["list", "--dir", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "key-a" in out and "app.M" in out

        assert cmd_snapshot(["inspect", "key-a", "--dir", str(tmp_path)]) == 0
        meta = json.loads(capsys.readouterr().out)
        assert meta["key"] == "key-a"
        assert meta["manifest"]["tag"] == "app.M"

        assert cmd_snapshot(["clear", "--dir", str(tmp_path)]) == 0
        assert store.entries() == []
        assert cmd_snapshot(["list", "--dir", str(tmp_path)]) == 0
        assert "no snapshots" in capsys.readouterr().out

    def test_clear_single_key(self, tmp_path):
        from modal_examples_tpu.core.cli import cmd_snapshot

        store = SnapshotStore(root=tmp_path)
        store.put("key-a", b"1")
        store.put("key-b", b"2")
        assert cmd_snapshot(["clear", "key-a", "--dir", str(tmp_path)]) == 0
        assert {e["key"] for e in store.entries()} == {"key-b"}
        assert cmd_snapshot(["clear", "key-a", "--dir", str(tmp_path)]) == 1

    def test_inspect_missing_key_errors(self, tmp_path):
        from modal_examples_tpu.core.cli import cmd_snapshot

        with pytest.raises(SystemExit):
            cmd_snapshot(["inspect", "nope", "--dir", str(tmp_path)])

    def test_dir_flag_requires_value(self):
        from modal_examples_tpu.core.cli import cmd_snapshot

        with pytest.raises(SystemExit, match="usage"):
            cmd_snapshot(["list", "--dir"])
