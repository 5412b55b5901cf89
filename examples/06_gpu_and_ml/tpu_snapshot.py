# # Fast cold starts: memory snapshots + persistent compile cache
#
# Counterpart of 06_gpu_and_ml/gpu_snapshot.py:41-52 (bge-small served with
# `@modal.enter(snap=True)` + GPU memory snapshots). `enable_memory_snapshot=
# True` is backed by a real checkpoint/restore subsystem
# (`modal_examples_tpu/snapshot/`): after the first container finishes its
# `@mtpu.enter(snap=True)` hooks, the worker serializes the object's state —
# the params pytree is captured as host numpy and re-put on device at restore
# — into a content-addressed store keyed by image digest + class source hash
# + env fingerprint + host architecture. Every later cold start restores that
# state and **skips the snap hooks entirely**: `load()` below runs once per
# code/image/env fingerprint, not once per container.
#
# Attrs that can't cross the snapshot boundary (jitted callables, clients,
# locks) are recorded as rebuild-on-restore markers — which is why the jit
# build + warmup lives in its own non-snap hook: a restored boot re-runs only
# `warmup()`, and with the **XLA persistent compile cache** warm that
# recompile is a disk hit (the single biggest TPU cold-start lever,
# SURVEY.md §7). Corrupted or stale snapshots fall back to a cold boot;
# restore is never less reliable than a cold start.
#
# Observe it: `tpurun snapshot list|inspect|clear` browses the store, and
# boot outcomes are exported as prometheus counters
# (`mtpu_snapshot_boots_total{result="hit|miss|fallback"}`).

import os

import modal_examples_tpu as mtpu

TPU = os.environ.get("MTPU_TPU", "") or None

app = mtpu.App("example-tpu-snapshot")


@app.cls(
    tpu=TPU,
    enable_memory_snapshot=True,
    timeout=600,
)
class Embedder:
    @mtpu.enter(snap=True)
    def load(self):
        """Snapshot-eligible: pure state (config + weights). A restored boot
        skips this hook — the captured pytree comes back from the store and
        is re-put on device."""
        import jax

        from modal_examples_tpu.models import bert

        self.cfg = bert.BertConfig.tiny()
        self.params = bert.init_params(jax.random.PRNGKey(0), self.cfg)

    @mtpu.enter()
    def warmup(self):
        """Runs on every boot — jitted callables can't cross the snapshot
        boundary. With the persistent compile cache warm (tpurun places it:
        JAX_COMPILATION_CACHE_DIR, else the in-checkout .xla_cache/), the
        recompile here is a disk hit instead of an XLA compile."""
        import time

        import jax
        import numpy as np

        from modal_examples_tpu.models import bert

        t0 = time.time()
        self._embed = jax.jit(lambda p, t: bert.embed(p, t, None, self.cfg))
        jax.block_until_ready(
            self._embed(self.params, np.zeros((4, 32), np.int32))
        )
        self.compile_s = time.time() - t0

    @mtpu.method()
    def embed(self, texts: list[str]) -> dict:
        import numpy as np

        from modal_examples_tpu.utils.tokenizer import ByteTokenizer

        tok = ByteTokenizer()
        ids = np.zeros((4, 32), np.int32)
        for i, t in enumerate(texts[:4]):
            enc = tok.encode(t)[:32]
            ids[i, : len(enc)] = enc
        out = self._embed(self.params, ids)
        return {"dim": int(out.shape[1]), "compile_s": self.compile_s}


@app.local_entrypoint()
def main():
    from modal_examples_tpu.utils.metrics import SNAPSHOT_BOOTS_METRIC
    from modal_examples_tpu.utils.prometheus import default_registry

    e = Embedder()
    r = e.embed.remote(["snapshot me"])
    print(f"embed dim={r['dim']}, warmup compile took {r['compile_s']:.2f}s")
    tag = "example-tpu-snapshot.Embedder"
    for result in ("hit", "miss", "fallback"):
        n = default_registry.value(
            SNAPSHOT_BOOTS_METRIC, {"function": tag, "result": result}
        )
        if n:
            print(f"snapshot boots: {result}={n:.0f}")
    print("next container boot restores load() from the snapshot store;")
    print("inspect it with `tpurun snapshot list`")
