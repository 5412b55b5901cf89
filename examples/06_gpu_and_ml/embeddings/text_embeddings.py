# # Text embeddings service (BGE on TPU)
#
# TPU-native counterpart of the reference's embeddings stack: where
# text_embeddings_inference.py:36-50 subprocess-spawns the TEI Rust/CUDA
# server and amazon_embeddings.py fans batches at it, this serves a JAX BGE
# encoder directly: an `@app.cls` with `@enter` weight load (load-once-serve-
# many), `@mtpu.batched` dynamic batching feeding fixed-shape TPU batches,
# `@mtpu.concurrent` input concurrency, and a web endpoint.
#
# Serve:  tpurun serve examples/06_gpu_and_ml/embeddings/text_embeddings.py
# Run:    tpurun run   examples/06_gpu_and_ml/embeddings/text_embeddings.py

import os

import modal_examples_tpu as mtpu

MODEL_DIR = os.environ.get("MTPU_MODEL_DIR")  # HF bge-small-en checkout
TPU = os.environ.get("MTPU_TPU", "") or None
MAX_SEQ = 128
MAX_BATCH = 32  # the ONE compiled batch shape: warmup, padding, batcher agree

app = mtpu.App("example-text-embeddings")

weights_vol = mtpu.Volume.from_name("bge-weights", create_if_missing=True)


def _build_model():
    import jax

    from modal_examples_tpu.models import bert

    if MODEL_DIR:
        cfg = bert.BertConfig.bge_small_en()
        params = bert.load_hf_weights(MODEL_DIR, cfg)
    else:  # dummy-weights dev mode (very_large_models.py:2-3 analog)
        cfg = bert.BertConfig.tiny()
        params = bert.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@app.cls(
    tpu=TPU,
    volumes={"/models": weights_vol},
    scaledown_window=300,
    max_containers=20,  # fleet scaling limits per text_embeddings_inference.py:79-87
    timeout=600,
)
@mtpu.concurrent(max_inputs=10)
class Embedder:
    @mtpu.enter()
    def load(self):
        import jax

        from modal_examples_tpu.models import bert
        from modal_examples_tpu.utils.tokenizer import load_tokenizer

        self.cfg, self.params = _build_model()
        self.tokenizer = load_tokenizer(MODEL_DIR)
        self.bert = bert
        self.jax = jax
        self._embed = jax.jit(
            lambda p, t, m: bert.embed(p, t, m, self.cfg)
        )
        # warmup compile at the one fixed batch shape
        import numpy as np

        t = np.zeros((MAX_BATCH, MAX_SEQ), np.int32)
        jax.block_until_ready(self._embed(self.params, t, np.ones_like(t)))

    def _encode_batch(self, texts: list[str]):
        import numpy as np

        if hasattr(self.tokenizer, "encode_batch"):
            # one native call builds the padded id/mask matrices
            toks, mask = self.tokenizer.encode_batch(texts, MAX_SEQ)
            toks = toks % self.cfg.vocab_size
        else:
            toks = np.full((len(texts), MAX_SEQ), 0, np.int32)
            mask = np.zeros((len(texts), MAX_SEQ), np.int32)
            for i, s in enumerate(texts):
                ids = self.tokenizer.encode(s)[:MAX_SEQ]
                toks[i, : len(ids)] = ids
                mask[i, : len(ids)] = 1
        # always pad to the single compiled shape: no serve-time retraces
        assert len(texts) <= MAX_BATCH, (len(texts), MAX_BATCH)
        pad_to = MAX_BATCH
        if pad_to != len(texts):
            toks = np.pad(toks, ((0, pad_to - len(texts)), (0, 0)))
            mask = np.pad(mask, ((0, pad_to - len(texts)), (0, 0)))
        out = self._embed(self.params, toks, mask)
        return [list(map(float, row)) for row in out[: len(texts)]]

    @mtpu.method()
    def embed_one(self, text: str) -> list[float]:
        return self._encode_batch([text])[0]

    @mtpu.batched(max_batch_size=MAX_BATCH, wait_ms=50)
    @mtpu.method()
    def embed(self, texts: list[str]) -> list[list[float]]:
        """Dynamic batching: concurrent callers' singles coalesce into one
        fixed-shape TPU batch (batched_whisper.py:127 pattern)."""
        return self._encode_batch(texts)


@app.function()
@mtpu.fastapi_endpoint(method="POST")
def embeddings(texts: list[str]) -> dict:
    """HTTP surface (TEI's /embed analog): POST {"texts": [...]}."""
    vecs = list(Embedder().embed.map(texts))
    return {"embeddings": vecs, "dim": len(vecs[0]) if vecs else 0}


@app.local_entrypoint()
def main():
    import math

    emb = Embedder()
    sents = [
        "The TPU systolic array multiplies matrices.",
        "Matrix multiplication runs on the MXU.",
        "I had soup for lunch today.",
    ]
    vecs = list(emb.embed.map(sents))
    def cos(a, b):
        return sum(x * y for x, y in zip(a, b))

    sim_close = cos(vecs[0], vecs[1])
    sim_far = cos(vecs[0], vecs[2])
    print(f"dim={len(vecs[0])}  sim(0,1)={sim_close:.3f}  sim(0,2)={sim_far:.3f}")
    for v in vecs:
        assert abs(math.fsum(x * x for x in v) - 1.0) < 1e-3  # normalized
    print("embeddings OK")
