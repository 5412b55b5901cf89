# # Vision-language serving: images in, streamed text out
#
# The TPU-native counterpart of the reference's VLM serving examples
# (06_gpu_and_ml/llm-serving/sglang_vlm.py — a Qwen-VL OpenAI endpoint via
# SGLang CUDA; chat_with_pdf_vision.py — image+text chat), built on our own
# stack end to end: a CLIP-style ViT tower + LLaVA projector (models.vlm)
# feeds projected patch embeddings into the llama engine's prefill as the
# first n_image_tokens positions, after which paged decode is completely
# unchanged — image tokens are just KV cache entries.
#
# Serve:   tpurun serve examples/06_gpu_and_ml/llm-serving/vlm_serving.py
# Client:  tpurun run   examples/06_gpu_and_ml/llm-serving/vlm_serving.py
#
# The OpenAI endpoint accepts standard multimodal content parts; images ride
# data: URIs (inline base64 — the server never fetches URLs). Cheap mode
# (default) serves a tiny random-weight model; point MTPU_MODEL_DIR /
# MTPU_VISION_DIR at HF checkouts (llama + CLIPVisionModel/LLaVA projector
# safetensors) for real weights.

import base64
import io
import json
import os
import time
import urllib.request

import modal_examples_tpu as mtpu

MODEL = os.environ.get("MTPU_MODEL", "tiny")
MODEL_DIR = os.environ.get("MTPU_MODEL_DIR")
VISION_DIR = os.environ.get("MTPU_VISION_DIR")  # CLIPVisionModel safetensors
PORT = int(os.environ.get("MTPU_PORT", "8000"))
TPU = os.environ.get("MTPU_TPU", "v5e-1") or None
MINUTES = 60

app = mtpu.App("example-vlm-serving")

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
class VLMServer:
    @mtpu.enter()
    def start(self):
        import jax

        from modal_examples_tpu.models import llama, vlm
        from modal_examples_tpu.serving import LLMEngine, OpenAIServer

        if MODEL_DIR:
            lcfg = llama.LlamaConfig.from_hf_config(f"{MODEL_DIR}/config.json")
        else:
            lcfg = llama.LlamaConfig.tiny()
        if VISION_DIR:
            vcfg = vlm.VLMConfig(
                vision=vlm.ViTConfig.clip_vit_l_14(), llm_dim=lcfg.dim
            )
            vparams = vlm.load_hf_vision_weights(VISION_DIR, vcfg)
        else:
            # dummy-weights dev mode (the reference's APP_USE_DUMMY_WEIGHTS
            # pattern, very_large_models.py:2-3)
            vcfg = vlm.VLMConfig(
                vision=vlm.ViTConfig.tiny(), llm_dim=lcfg.dim
            )
            vparams = vlm.init_vision_params(jax.random.PRNGKey(1), vcfg)

        engine = LLMEngine(
            lcfg,
            model_dir=MODEL_DIR,
            max_slots=8 if MODEL_DIR else 4,
            max_model_len=1024 if MODEL_DIR else 128,
            prefill_buckets=(128, 256, 512, 1024) if MODEL_DIR else (32, 64),
            vision=(vcfg, vparams),
        )
        self.server = OpenAIServer(engine, model_name=f"{MODEL}-vlm", port=PORT)
        self.server.start()

    @mtpu.exit()
    def shutdown(self):
        self.server.stop()


# ## Client — post a generated image as a data: URI content part


def _png_data_uri() -> str:
    """A tiny synthetic image (no egress): colored gradient PNG."""
    import numpy as np
    from PIL import Image

    h = w = 64
    y, x = np.mgrid[0:h, 0:w]
    arr = np.stack(
        [255 * x / w, 255 * y / h, 128 + 64 * np.sin(x / 7)], axis=-1
    ).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="PNG")
    return "data:image/png;base64," + base64.b64encode(buf.getvalue()).decode()


@app.local_entrypoint()
def main(prompt: str = "Describe this image.", max_tokens: int = 32):
    url = VLMServer.serve()
    deadline = time.time() + 180
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
            "messages": [
                {
                    "role": "user",
                    "content": [
                        {"type": "text", "text": prompt},
                        {
                            "type": "image_url",
                            "image_url": {"url": _png_data_uri()},
                        },
                    ],
                }
            ],
            "max_tokens": max_tokens,
            "temperature": 0.0,
        }
    ).encode()
    req = urllib.request.Request(
        f"{url}/v1/chat/completions",
        data=body,
        headers={"content-type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=300) as r:
        out = json.loads(r.read())
    print("assistant:", out["choices"][0]["message"]["content"])
    print("usage:", out["usage"])
