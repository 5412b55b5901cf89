# # OpenAI-compatible LLM serving on TPU
#
# The north-star serving example — the TPU-native counterpart of the
# reference's 06_gpu_and_ml/llm-serving/vllm_inference.py (structure cited
# per SURVEY.md §3.2). Where the reference subprocess-spawns `vllm serve`
# (CUDA paged attention + CUDA graphs), this serves through our own JAX
# engine: continuous batching over fixed decode slots, Pallas ragged paged
# attention, sampling fused into the jitted decode step.
#
# Deploy:  tpurun serve examples/06_gpu_and_ml/llm-serving/llm_inference.py
# Client:  tpurun run  examples/06_gpu_and_ml/llm-serving/llm_inference.py
#
# FAST_BOOT analog (vllm_inference.py:85-101): MTPU_MODEL=tiny serves a tiny
# random-weight model (the dummy-weights dev mode, very_large_models.py:2-3);
# point MTPU_MODEL_DIR at an HF llama checkout for real weights.

import json
import os
import time
import urllib.request

import modal_examples_tpu as mtpu

MODEL = os.environ.get("MTPU_MODEL", "tiny")
MODEL_DIR = os.environ.get("MTPU_MODEL_DIR")  # HF safetensors dir on a Volume
PORT = int(os.environ.get("MTPU_PORT", "8000"))
# resource spec; MTPU_TPU="" runs the server container on CPU (dev mode)
TPU = os.environ.get("MTPU_TPU", "v5e-1") or None
# tensor parallelism: one flag on the same engine, like the reference's
# --tensor-parallel-size (vllm_inference.py:179-180). MTPU_TP=2 shards
# weights (Megatron layout) + the paged KV cache (by kv head) over a
# "tensor" mesh axis; XLA inserts the ICI collectives.
TP = int(os.environ.get("MTPU_TP", "1"))
# speculative decoding: draft-model gamma, like the reference's
# --speculative-config (vllm_inference.py:196-205). MTPU_SPEC_GAMMA=4 with
# MTPU_SPEC_DRAFT naming a preset enables it; point MTPU_SPEC_DRAFT_DIR at
# an HF checkout for real draft weights. Draft and target must share a
# vocabulary (the engine validates).
SPEC_GAMMA = int(os.environ.get("MTPU_SPEC_GAMMA", "0"))
SPEC_DRAFT = os.environ.get("MTPU_SPEC_DRAFT", "tiny")
SPEC_DRAFT_DIR = os.environ.get("MTPU_SPEC_DRAFT_DIR")
# weight-only quantization (the bitsandbytes/unsloth 4-bit analog):
# MTPU_QUANT=int8|int4 halves/quarters weight HBM traffic and composes
# with MTPU_TP (quantized trees shard under tensor parallelism)
QUANT = os.environ.get("MTPU_QUANT") or None
MINUTES = 60

app = mtpu.App("example-llm-inference")

# HF weights live on a Volume, like the reference's huggingface-cache volume
# (vllm_inference.py:77-81). The XLA compile cache (the vllm-cache analog, and
# the biggest cold-start lever on TPU) is placed by the entry point: `tpurun`
# exports JAX_COMPILATION_CACHE_DIR (the fixed in-checkout .xla_cache/ unless
# the environment already names a directory) and the container inherits it.
hf_cache_vol = mtpu.Volume.from_name("huggingface-cache", create_if_missing=True)

image = mtpu.Image.tpu_base()


@app.server(
    port=PORT,
    tpu=TPU,
    image=image,
    volumes={"/root/.cache/huggingface": hf_cache_vol},
    startup_timeout=20 * MINUTES,
    scaledown_window=15 * MINUTES,
    target_concurrency=100,
    unauthenticated=True,
)
class LLMServer:
    @mtpu.enter()
    def start(self):
        from modal_examples_tpu.serving import OpenAIServer, build_engine

        engine_kw = {}
        if TP > 1:
            import jax

            from modal_examples_tpu.parallel import make_mesh

            engine_kw["mesh"] = make_mesh(
                {"tensor": TP}, devices=jax.devices()[:TP]
            )
        if SPEC_GAMMA > 0:
            engine_kw["speculative"] = (SPEC_DRAFT, SPEC_GAMMA)
            if SPEC_DRAFT_DIR:
                engine_kw["draft_model_dir"] = SPEC_DRAFT_DIR
        engine = build_engine(
            MODEL,
            model_dir=MODEL_DIR,
            max_slots=8 if MODEL != "tiny" else 4,
            max_model_len=1024 if MODEL != "tiny" else 128,
            quantization=QUANT,
            **engine_kw,
        )
        self.server = OpenAIServer(engine, model_name=MODEL, port=PORT)
        self.server.start()  # replica advertised once the port accepts

    @mtpu.exit()
    def shutdown(self):
        self.server.stop()


# ## Client — health-check then a real request, like the reference's
# local_entrypoint smoke test (vllm_inference.py:243-345)


@app.local_entrypoint()
def main(prompt: str = "A neutron star is", max_tokens: int = 32, stream: bool = False):
    url = LLMServer.serve()
    deadline = time.time() + 120
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(f"{url}/health", timeout=2) as r:
                if json.load(r).get("status") == "ok":
                    break
        except Exception:
            time.sleep(1)
    else:
        raise TimeoutError("server never became healthy")
    print(f"server healthy at {url}")

    body = json.dumps(
        {
            "messages": [{"role": "user", "content": prompt}],
            "max_tokens": max_tokens,
            "temperature": 0.8,
            "stream": stream,
        }
    ).encode()
    req = urllib.request.Request(
        f"{url}/v1/chat/completions",
        data=body,
        headers={"content-type": "application/json"},
    )
    t0 = time.time()
    with urllib.request.urlopen(req) as r:
        if stream:
            for line in r:
                line = line.decode().strip()
                if line.startswith("data: ") and line != "data: [DONE]":
                    delta = json.loads(line[6:])["choices"][0]["delta"]
                    print(delta.get("content", ""), end="", flush=True)
            print()
        else:
            out = json.load(r)
            print("completion:", repr(out["choices"][0]["message"]["content"]))
            print("usage:", out["usage"])
    print(f"round-trip: {time.time() - t0:.2f}s")
    LLMServer.stop()
