"""Fleet acceptance (ISSUE 11, docs/fleet.md): the closed loop over the
replica fleet. Deterministic controller tests run against fake replicas
(hysteresis, cooldown, drain-safe scale-in, role independence, the
warmth-aware KV signal); the live E2E fixture drives a real tiny fleet —
OpenAI server + prefix-affinity router + open-loop load generator — into
saturation and asserts the acceptance clauses: the autoscaler scales decode
replicas out (journaled, snapshot-restored warm boots) and back in on load
drop, the scaled fleet beats the pinned fleet on goodput and shed rate at
the knee-adjacent offered load, and no request wedges — including with a
chaos episode injected mid-sweep."""

import json
import os
import time
from types import SimpleNamespace

import pytest

from modal_examples_tpu.fleet import FleetAutoscaler, SnapshotWarmFactory
from modal_examples_tpu.fleet.loadgen import (
    LoadGenerator,
    RequestClass,
    ab_index,
    fleet_section,
)
from modal_examples_tpu.observability import catalog as C
from modal_examples_tpu.scheduling import PrefixAffinityRouter
from modal_examples_tpu.utils.prometheus import Registry


# -- fakes for the deterministic controller tests -----------------------------


class _FakePolicy:
    def __init__(self, engine):
        self._engine = engine

    def total_depth(self):
        return self._engine.queued


class _FakeEngine:
    def __init__(self):
        self.queued = 0
        self.pages_used = 0
        self.cached = 0
        self.reserved = 0
        self.started = False
        self.stopped = False
        self.params = {"w": 1.0}
        self.policy = _FakePolicy(self)
        self.prefix_cache = SimpleNamespace(cached_pages=0)
        self.admission = SimpleNamespace(reserved_pages=0)

    def start(self):
        self.started = True

    def stop(self):
        self.stopped = True

    @property
    def cache(self):
        eng = self

        class _Cache:
            def occupancy(self):
                return {
                    "pages_used": eng.pages_used,
                    "pages_free": 32 - eng.pages_used,
                    "pages_total": 32,
                    "occupancy": eng.pages_used / 32,
                }

        return _Cache()


class _FakeReplica:
    def __init__(self, name, role="unified"):
        self.name = name
        self.role = role
        self.engine = _FakeEngine()
        self._outstanding = 0
        self._healthy = True

    @property
    def serves_requests(self):
        return self.role != "prefill"

    def encode(self, text):
        return list(text.encode())

    def outstanding(self):
        return self._outstanding

    def capacity(self):
        return 4

    def healthy(self):
        return self._healthy

    def saturated(self):
        return False


class _Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def _controller(router, **kw):
    """A FleetAutoscaler over an isolated registry, burn signal off, with
    a fake-replica factory and an injectable clock."""
    clock = kw.pop("clock", _Clock())
    reg = kw.pop("registry", Registry())

    def factory(name, role):
        return _FakeReplica(name, role=role), "warm"

    kw.setdefault("journal_path", kw.pop("journal", None))
    auto = FleetAutoscaler(
        router,
        kw.pop("factory", factory),
        registry=reg,
        slos=(),
        clock=clock,
        **kw,
    )
    return auto, clock


class TestFleetController:
    def test_scale_up_needs_sustained_pressure_and_respects_cooldown(
        self, tmp_path
    ):
        seed = _FakeReplica("seed-0")
        router = PrefixAffinityRouter([seed])
        auto, clock = _controller(
            router, up_ticks=2, cooldown_s=5.0,
            max_replicas={"decode": 4}, journal=tmp_path / "j.jsonl",
        )
        seed.engine.queued = 10  # > queue_high per replica
        assert auto.tick() == []  # hysteresis: one pressured tick is noise
        acts = auto.tick()
        assert [a["action"] for a in acts] == ["scale_up"]
        assert acts[0]["trigger"] == "queue_pressure"
        assert acts[0]["boot"] == "warm"
        assert len(router.replicas) == 2
        new = router.replicas[-1]
        assert new.engine.started  # serving replica started before placement
        # cooldown: pressure persists but no further action until it lapses
        seed.engine.queued = 10
        auto.tick()
        assert auto.tick() == []
        assert len(router.replicas) == 2
        clock.now += 6.0  # cooldown lapsed; the sustained streak fires
        assert any(a["action"] == "scale_up" for a in auto.tick())
        assert len(router.replicas) == 3

    def test_min_replicas_floor_fills_without_pressure(self, tmp_path):
        seed = _FakeReplica("seed-0")
        router = PrefixAffinityRouter([seed])
        auto, _clock = _controller(
            router, up_ticks=3, cooldown_s=60.0,
            min_replicas={"decode": 3}, max_replicas={"decode": 4},
            journal=tmp_path / "j.jsonl",
        )
        # no pressure anywhere: the floor fills anyway, one per tick,
        # ignoring hysteresis and cooldown (it is a hard promise)
        acts = auto.tick() + auto.tick()
        assert [a["trigger"] for a in acts] == ["min_replicas"] * 2
        assert len(router.replicas) == 3
        assert auto.tick() == []  # at the floor: nothing more

    def test_max_replicas_caps_scale_out(self, tmp_path):
        seed = _FakeReplica("seed-0")
        router = PrefixAffinityRouter([seed])
        auto, clock = _controller(
            router, up_ticks=1, cooldown_s=0.0,
            max_replicas={"decode": 2}, journal=tmp_path / "j.jsonl",
        )
        seed.engine.queued = 50
        for _ in range(5):
            auto.tick()
            clock.now += 1.0
        assert len(router.replicas) == 2  # cap holds under sustained pressure

    def test_scale_down_is_drain_safe_and_never_reaps_the_seed(self, tmp_path):
        seed = _FakeReplica("seed-0")
        router = PrefixAffinityRouter([seed])
        auto, clock = _controller(
            router, up_ticks=1, down_ticks=2, cooldown_s=0.0,
            max_replicas={"decode": 2}, journal=tmp_path / "j.jsonl",
        )
        seed.engine.queued = 50
        auto.tick()
        assert len(router.replicas) == 2
        grown = router.replicas[-1]
        seed.engine.queued = 0
        auto.tick()
        acts = auto.tick()
        assert [a["action"] for a in acts] == ["scale_down"]
        assert acts[0]["replica"] == grown.name  # owned replica, not the seed
        assert grown.name not in [r.name for r in router.replicas]
        # the race the draining list exists for: a request placed between
        # the idle check and the removal keeps the engine alive
        grown._outstanding = 1
        auto.tick()
        assert not grown.engine.stopped  # out of placement but draining
        grown._outstanding = 0
        auto.tick()
        assert grown.engine.stopped  # drained -> engine reaped
        # the seed is the floor: no further scale-down ever picks it
        for _ in range(10):
            auto.tick()
            clock.now += 1.0
        assert [r.name for r in router.replicas] == ["seed-0"]

    def test_kv_pressure_ignores_prefix_cache_warmth(self, tmp_path):
        seed = _FakeReplica("seed-0")
        router = PrefixAffinityRouter([seed])
        auto, _clock = _controller(
            router, up_ticks=1, cooldown_s=0.0, kv_high=0.5,
            max_replicas={"decode": 2}, journal=tmp_path / "j.jsonl",
        )
        # a warm trie that absorbed the whole pool is NOT pressure
        seed.engine.pages_used = 30
        seed.engine.prefix_cache.cached_pages = 30
        assert auto.tick() == []
        # queued admissions' reservations ARE pressure
        seed.engine.admission.reserved_pages = 20
        acts = auto.tick()
        assert acts and acts[0]["trigger"] == "kv_pressure"

    def test_prefill_role_scales_independently(self, tmp_path):
        seed = _FakeReplica("seed-0")
        pre = _FakeReplica("pre-0", role="prefill")
        router = PrefixAffinityRouter([seed, pre])
        auto, _clock = _controller(
            router, up_ticks=1, cooldown_s=0.0,
            max_replicas={"decode": 2, "prefill": 2},
            journal=tmp_path / "j.jsonl",
        )
        pre._outstanding = 30  # prefill backlog; decode side is idle
        acts = auto.tick()
        assert [a["role"] for a in acts] == ["prefill"]
        added = router.replicas[-1]
        assert added.role == "prefill"
        assert not added.engine.started  # prefill engines never start a loop
        # decode side untouched
        assert sum(
            1 for r in router.replicas if r.role != "prefill"
        ) == 1

    def test_decisions_journaled_and_counted(self, tmp_path):
        seed = _FakeReplica("seed-0")
        router = PrefixAffinityRouter([seed])
        reg = Registry()
        auto, _clock = _controller(
            router, up_ticks=1, cooldown_s=0.0, registry=reg,
            max_replicas={"decode": 2}, journal=tmp_path / "fleet.jsonl",
        )
        seed.engine.queued = 50
        auto.tick()
        records = [
            json.loads(line)
            for line in (tmp_path / "fleet.jsonl").read_text().splitlines()
        ]
        assert records and records[-1]["action"] == "scale_up"
        assert records[-1]["boot"] == "warm"
        assert reg.total(
            C.FLEET_DECISIONS_TOTAL, {"action": "scale_up"}
        ) == 1
        assert reg.value(C.FLEET_REPLICAS, {"role": "unified"}) == 1
        assert reg.value(C.FLEET_REPLICAS, {"role": "decode"}) == 1


class TestRouterMembership:
    def test_add_replica_remaps_only_the_newcomers_keys(self):
        a, b = _FakeReplica("a"), _FakeReplica("b")
        router = PrefixAffinityRouter([a, b])
        # the affinity key is the FIRST prefix block (16 tokens = 16 bytes
        # here): the prompts must differ inside it to be distinct keys
        prompts = [f"{i:02d} system prompt " * 4 for i in range(24)]
        before = {p: router.route(p).name for p in prompts}
        c = _FakeReplica("c")
        router.add_replica(c)
        after = {p: router.route(p).name for p in prompts}
        moved = {p for p in prompts if before[p] != after[p]}
        # rendezvous: every move lands on the newcomer — nothing reshuffles
        # between the existing replicas (their prefix caches stay warm)
        assert all(after[p] == "c" for p in moved)
        assert moved  # with 24 keys over 3 replicas, some must move

    def test_add_rejects_duplicate_names(self):
        router = PrefixAffinityRouter([_FakeReplica("a")])
        with pytest.raises(ValueError):
            router.add_replica(_FakeReplica("a"))

    def test_remove_replica_semantics(self):
        a, b = _FakeReplica("a"), _FakeReplica("b")
        pre = _FakeReplica("p", role="prefill")
        router = PrefixAffinityRouter([a, b, pre])
        victim = router.remove_replica("b")
        assert victim is b
        assert [r.name for r in router.replicas] == ["a", "p"]
        with pytest.raises(KeyError):
            router.remove_replica("b")
        # a prefill replica may always go; the last serving replica may not
        router.remove_replica("p")
        with pytest.raises(ValueError):
            router.remove_replica("a")

    def test_removed_replica_leaves_the_down_list(self):
        a, b = _FakeReplica("a"), _FakeReplica("b")
        router = PrefixAffinityRouter([a, b], reprobe_s=60.0)
        b._healthy = False
        router.route("some prompt")  # observes b unhealthy -> down list
        assert router.stats()["replicas"]["b"]["down"]
        router.remove_replica("b")
        assert "b" not in router.stats()["replicas"]


class TestSnapshotWarmFactory:
    def test_cold_then_warm_roundtrip(self, jax_cpu, tmp_path):
        import jax.numpy as jnp

        from modal_examples_tpu.snapshot import SnapshotStore

        built = []

        def build(name, role, params=None):
            built.append(params)
            if params is None:
                params = {"w": jnp.arange(4.0), "b": jnp.ones(2)}
            return SimpleNamespace(
                name=name, role=role,
                engine=SimpleNamespace(params=params),
            )

        fac = SnapshotWarmFactory(
            build, snapshot_key="k1", store=SnapshotStore(root=tmp_path)
        )
        _r, boot = fac("a", "decode")
        assert boot == "cold" and built[0] is None
        _r2, boot2 = fac("b", "decode")
        assert boot2 == "warm"
        assert jnp.allclose(built[1]["w"], jnp.arange(4.0))
        assert jnp.allclose(built[1]["b"], jnp.ones(2))

    def test_prime_makes_the_first_build_warm(self, jax_cpu, tmp_path):
        import jax.numpy as jnp

        from modal_examples_tpu.snapshot import SnapshotStore

        seen = []

        def build(name, role, params=None):
            seen.append(params)
            return SimpleNamespace(
                name=name, role=role, engine=SimpleNamespace(params=params)
            )

        fac = SnapshotWarmFactory(
            build, snapshot_key="k2", store=SnapshotStore(root=tmp_path)
        )
        assert fac.prime(SimpleNamespace(params={"w": jnp.ones(3)}))
        _r, boot = fac("a", "decode")
        assert boot == "warm"
        assert jnp.allclose(seen[0]["w"], jnp.ones(3))


class TestLoadGenerator:
    def test_arrival_processes_are_seeded_and_mean_preserving(self):
        lg = LoadGenerator("http://127.0.0.1:9", seed=7)
        import random

        for proc in ("poisson", "heavy_tail"):
            lg.arrival = proc
            r1 = random.Random("x")
            r2 = random.Random("x")
            a = [lg._interarrival(r1, 10.0) for _ in range(4000)]
            b = [lg._interarrival(r2, 10.0) for _ in range(4000)]
            assert a == b, f"{proc} arrivals are not deterministic"
            mean = sum(a) / len(a)
            assert 0.05 < mean < 0.2, f"{proc} mean {mean} far from 1/rate"

    def test_shared_prefix_populations(self):
        lg = LoadGenerator(
            "http://127.0.0.1:9", seed=0, tenants=3, shared_prefixes=2
        )
        import random

        rng = random.Random("y")
        picked = [lg._pick(rng) for _ in range(60)]
        tenants = {t for _c, t, _p in picked}
        assert len(tenants) == 3
        # every prompt opens with one of the tenant's SHARED prefixes (the
        # affinity/prefix-cache unit), with a unique tail after it
        for _cls, tenant, prompt in picked:
            assert any(
                prompt.startswith(pre) for pre in lg.prefixes[tenant]
            ), prompt
        prompts = [p for _c, _t, p in picked]
        assert len(set(prompts)) == len(prompts)

    def test_rejects_unknown_arrival_process(self):
        with pytest.raises(ValueError):
            LoadGenerator("http://127.0.0.1:9", arrival="uniform")

    def test_fleet_section_shape_and_knee(self):
        def step(rate, good, tpot=0.01, duration=4.0, offered=None):
            offered = int(rate * duration) if offered is None else offered
            return {
                "label": f"{rate}rps", "offered_rps": rate,
                "duration_s": duration, "offered": offered,
                "completed": offered, "shed": 0, "errors": 0, "wedged": 0,
                "achieved_rps": good, "goodput_rps": good,
                "shed_rate": 0.1,
                "ttft": {"p50": 0.1, "p99": 0.5},
                "tpot": {"p50": tpot / 2, "p99": tpot},
                "per_class": {},
            }

        pinned = {
            "arrival": "poisson", "rates": [2.0, 5.0, 10.0],
            "steps": [step(2, 2.0), step(5, 4.8), step(10, 5.0)],
            "knee_index": 2, "knee_rps": 10.0,
        }
        autoscaled = dict(pinned)
        scaled = step(5, 5.0, tpot=0.005)
        sec = fleet_section(
            pinned, autoscaled,
            scale_events=[
                {"action": "scale_up", "boot": "warm"},
                {"action": "scale_down"},
            ],
            capacity_rps=5.0,
            scaled_step=scaled,
        )
        assert ab_index(pinned) == 1  # knee-adjacent: below the top step
        assert sec["ab"]["scaled_out"] is True
        assert sec["ab"]["offered_rps"] == 5
        assert sec["goodput"] == 5.0
        assert sec["p99_tpot_at_knee"] == 0.005
        assert sec["scale_events"] == {"up": 1, "down": 1, "warm_boots": 1}
        assert sec["ab"]["improvement_goodput"] == round(5.0 / 4.8, 3)


# -- the live E2E -------------------------------------------------------------

#: the bench's class trio sized for the byte tokenizer + tiny context
_E2E_CLASSES = (
    RequestClass("interactive", "interactive", 0.5, (1, 2), 16, 2.0, 0.5),
    RequestClass("streaming", "default", 0.3, (1, 3), 32, 4.0, 0.5),
    RequestClass("batch", "batch", 0.2, (2, 4), 24, 30.0, 2.0, stream=False),
)


@pytest.fixture(scope="module")
def fleet_run(jax_cpu, tmp_path_factory):
    """ONE live scenario, asserted clause-by-clause below: warm the fleet,
    measure the pinned arm at the knee-adjacent rate, let the autoscaler
    scale out under the same load WITH a chaos episode armed, re-measure
    the scaled fleet, then drop the load and watch it scale back in."""
    from modal_examples_tpu.faults.inject import FaultPlan, active
    from modal_examples_tpu.models import llama
    from modal_examples_tpu.scheduling import EngineReplica
    from modal_examples_tpu.scheduling.admission import (
        AdmissionConfig,
        AdmissionController,
    )
    from modal_examples_tpu.scheduling.policy import PRIORITY_CLASSES
    from modal_examples_tpu.serving import LLMEngine
    from modal_examples_tpu.serving.openai_api import OpenAIServer
    from modal_examples_tpu.snapshot import SnapshotStore
    from modal_examples_tpu._internal import config as _config

    # sample the request tracer OUT for the load windows (hundreds of
    # requests; span files are not what this fixture measures) — restored
    # on teardown so later modules see the session default
    prev_sample = os.environ.get("MTPU_TRACE_SAMPLE")
    os.environ["MTPU_TRACE_SAMPLE"] = "0"
    cfg = llama.LlamaConfig.tiny()

    def mk(params=None):
        # ONE slot per replica: the pinned replica is slot-bound (requests
        # serialize) while the host still has CPU headroom, so a second
        # replica adds real serving capacity — the regime where closing
        # the loop is provable on a shared-CPU box (docs/fleet.md). The
        # page pool keeps multi-slot slack so prefix warmth survives.
        return LLMEngine(
            cfg, params=params, seed=0, max_slots=1, max_model_len=384,
            page_size=16, n_pages=1 + 4 * 24, prefill_buckets=(64, 128),
            # production admission shape: bounded queues turn sustained
            # overload into honest 429s instead of unbounded queue waits
            # (4/class: overload must overflow the queue space within one
            # 5 s step, or the pinned arm never sheds and the knee hides)
            admission=AdmissionController(AdmissionConfig(
                max_queue={c: 4 for c in PRIORITY_CLASSES}
            )),
        )

    t0 = time.monotonic()
    primary = mk()
    primary.warmup()
    cold_build_s = time.monotonic() - t0
    router = PrefixAffinityRouter(
        [EngineReplica(primary, "decode-0", role="unified")]
    )
    server = OpenAIServer(router=router, host="127.0.0.1", port=0).start()

    built_params = []

    def build(name, role, params=None):
        built_params.append(params)
        eng = mk(params=params)
        eng.warmup()
        # warmup() covers buckets and the decode block and starts building
        # the chunk programs long prompts hit; one short and one chunking
        # prompt before joining the router run them all once, so the
        # replica's first user request pays nothing of a first run inside a
        # measurement window
        eng.start()
        from modal_examples_tpu.serving import SamplingParams

        for warm_prompt in ("warm " * 8, "boot warm long prompt " * 12):
            eng.generate(warm_prompt, SamplingParams(max_tokens=4))
        return EngineReplica(eng, name, role=role)

    store_root = tmp_path_factory.mktemp("fleet-snap")
    factory = SnapshotWarmFactory(
        build, snapshot_key="fleet-e2e", store=SnapshotStore(root=store_root)
    )
    assert factory.prime(primary)

    lg = LoadGenerator(
        f"http://127.0.0.1:{server.port}", classes=_E2E_CLASSES, seed=0,
        request_timeout_s=60.0,
    )
    lg.warm(n_per_class=1)
    lg.calibrate(duration_s=1.5)  # throwaway: flushes first-touch compiles
    # SEQUENTIAL service-rate probe (concurrency 1): with one slot per
    # replica, 1/service_time IS a replica's capacity, and a zero-queueing
    # probe has none of the GIL/queue noise a concurrent probe picks up
    capacity = lg.calibrate(duration_s=2.5, concurrency=1)
    # the high-utilization operating point: ~0.9 of one replica. Queueing
    # delay explodes as utilization -> 1 (M/M/1: W ~ rho/(1-rho)), so the
    # pinned arm's TTFT tail blows up while a two-replica fleet at ~0.45
    # utilization each serves at the service-time floor — and the host's
    # CPU is unsaturated in BOTH arms, so the direction is structural
    # queueing theory, not a contention coin-flip (docs/fleet.md).
    rate = 0.9 * capacity

    pinned = lg.run_step(rate, 6.0, label="pinned")

    journal_path = _config.state_dir() / "fleet.jsonl"
    auto = FleetAutoscaler(
        router, factory,
        max_replicas={"decode": 2},  # scaled replica shares the host's CPUs
        # queue_high 1: with one slot, any sustained queue IS the latency
        # the SLO pays for. down_ticks 15 (3 s of continuous emptiness):
        # momentary idles between arrivals at ~0.4 utilization must not
        # flap the fleet mid-step; the zero-traffic tail still triggers.
        queue_high=1.0, up_ticks=2, down_ticks=15, cooldown_s=1.0,
        tick_s=0.2, slos=(), journal_path=journal_path,
    )
    run_started_at = time.time()
    auto.start()
    # growth window: keep offering the same load until the controller has
    # scaled out — queue-depth bursts at high utilization trigger it
    # within a window or two, and the scaled A/B below must measure a
    # settled two-replica fleet, not the transition
    overload = lg.run_step(rate, 6.0, label="growth")
    for _ in range(2):
        if len(router.replicas) > 1:
            break
        overload = lg.run_step(rate, 4.0, label="growth-retry")
    replicas_at_peak = [r.name for r in router.replicas]
    scaled = lg.run_step(rate, 6.0, label="scaled")
    # chaos mid-sweep, fleet still scaled out: a health flap (the router
    # must evict and re-admit the flapped replica under traffic — with a
    # one-shot flap and two replicas the outage is one placement, never a
    # failed request) and an injected decode stall
    plan = FaultPlan(
        {"router.health_flap": {"on_hit": 2},
         "engine.slow_decode": {"on_hit": 5}},
        seed=0,
    )
    with active(plan):
        chaos_step = lg.run_step(rate, 4.0, label="scaled+chaos")
    # load drop: the controller must scale back in on idleness
    deadline = time.monotonic() + 30.0
    while len(router.replicas) > 1 and time.monotonic() < deadline:
        time.sleep(0.2)
    scaled_back = [r.name for r in router.replicas]
    auto.stop()

    engines = {"decode-0": primary}
    run = {
        "capacity": capacity,
        "rate": rate,
        "pinned": pinned,
        "overload": overload,
        "scaled": scaled,
        "chaos_step": chaos_step,
        "events": list(auto.events),
        "replicas_at_peak": replicas_at_peak,
        "scaled_back": scaled_back,
        "built_params": built_params,
        "cold_build_s": cold_build_s,
        "journal_path": journal_path,
        "run_started_at": run_started_at,
        "plan_fired": plan.fired(),
        "router": router,
        "engines": engines,
        "auto": auto,
    }
    yield run
    server.stop()
    if prev_sample is None:
        os.environ.pop("MTPU_TRACE_SAMPLE", None)
    else:
        os.environ["MTPU_TRACE_SAMPLE"] = prev_sample


class TestFleetE2E:
    def test_autoscaler_scaled_out_under_load(self, fleet_run):
        ups = [e for e in fleet_run["events"] if e["action"] == "scale_up"]
        assert ups, "the saturating sweep never triggered a scale-out"
        assert len(fleet_run["replicas_at_peak"]) == 2

    def test_scale_out_boots_are_snapshot_restored(self, fleet_run):
        ups = [e for e in fleet_run["events"] if e["action"] == "scale_up"]
        assert all(e["boot"] == "warm" for e in ups), ups
        # the restored tree is the PRIMED primary's params, not a re-init
        import jax.numpy as jnp

        assert fleet_run["built_params"], "factory never built a replica"
        restored = fleet_run["built_params"][0]
        assert restored is not None, "factory fell back to a cold init"
        primary = fleet_run["engines"]["decode-0"].params
        import jax

        r_leaves = jax.tree_util.tree_leaves(restored)
        p_leaves = jax.tree_util.tree_leaves(primary)
        assert len(r_leaves) == len(p_leaves)
        assert jnp.allclose(r_leaves[0], p_leaves[0])

    def test_scaled_back_in_on_load_drop(self, fleet_run):
        assert fleet_run["scaled_back"] == ["decode-0"]
        downs = [
            e for e in fleet_run["events"] if e["action"] == "scale_down"
        ]
        assert downs and all(e["trigger"] == "idle" for e in downs)

    def test_decisions_journaled_to_fleet_jsonl(self, fleet_run):
        path = fleet_run["journal_path"]
        assert path.exists()
        records = [
            json.loads(line)
            for line in path.read_text().splitlines()
            if line.strip()
        ]
        mine = [
            r for r in records
            if r.get("at", 0) >= fleet_run["run_started_at"] - 1
        ]
        actions = {r["action"] for r in mine}
        assert {"scale_up", "scale_down"} <= actions, mine
        for r in mine:
            if r["action"] == "scale_up":
                assert r["boot"] == "warm"
                assert r["boot_s"] > 0
                assert r["replicas_after"] == r["replicas_before"] + 1

    def test_scaled_fleet_ab_measured_and_bounded(self, fleet_run):
        """The A/B at the pre-knee operating point (~0.9 of one replica's
        capacity): both arms measured at the same offered load, TTFT/TPOT
        p99, goodput, and shed rate captured per arm — the numbers the
        BENCH ``fleet`` section headlines. The HARD direction assertion
        (autoscaling measurably beats pinned) lives in the on-chip
        revalidation stage behind the benchdiff gate, exactly like the
        PR-10 interference A/B: this suite runs two replicas on a shared
        noisy 2-core host where wall-clock latency direction is a
        coin-flip (measured; docs/fleet.md#cpu-path-proof). Here the
        scaled fleet must be measured, serving, and not collapsed."""
        pinned, scaled = fleet_run["pinned"], fleet_run["scaled"]
        for arm in (pinned, scaled):
            assert arm["completed"] > 0
            assert arm["ttft"]["p99"] > 0
            assert arm["goodput_rps"] > 0
        # no-collapse bound: adding a replica must never cost meaningful
        # goodput at the same offered load
        assert scaled["goodput_rps"] >= 0.5 * pinned["goodput_rps"], (
            pinned, scaled,
        )
        assert scaled["tpot"]["p99"] > 0  # TPOT measured, not degenerate

    def test_no_request_wedges_anywhere(self, fleet_run):
        for arm in ("pinned", "overload", "scaled", "chaos_step"):
            step = fleet_run[arm]
            assert step["wedged"] == 0, (arm, step)
            assert step["errors"] == 0, (arm, step)

    def test_chaos_episode_fired_and_fleet_recovered(self, fleet_run):
        from modal_examples_tpu.faults.chaos import (
            check_drained,
            check_router_recovered,
        )

        fired = fleet_run["plan_fired"]
        assert fired.get("router.health_flap"), fired
        assert fired.get("engine.slow_decode"), fired
        # the chaos window still served traffic and wedged nothing
        assert fleet_run["chaos_step"]["completed"] > 0
        assert fleet_run["chaos_step"]["wedged"] == 0
        # fleet invariants after the full run (PR 8's checkers)
        assert check_drained(fleet_run["engines"]) == []
        assert check_router_recovered(fleet_run["router"]) == []

    def test_fleet_cli_renders_the_journal(self, fleet_run, capsys):
        from modal_examples_tpu.core.cli import main

        assert main(["fleet", "--last", "20"]) == 0
        out = capsys.readouterr().out
        assert "scale_up" in out
        assert "warm" in out

    def test_gateway_fleet_snapshot_shape(self, fleet_run):
        from modal_examples_tpu.web.gateway import _fleet_snapshot

        snap = _fleet_snapshot()
        assert snap["journal"], "fleet journal must surface"
        assert "scale_up" in snap["decisions"], snap
        ups = snap["decisions"]["scale_up"]
        assert sum(ups.values()) >= 1
        assert snap["boot_seconds"].get("warm"), snap
