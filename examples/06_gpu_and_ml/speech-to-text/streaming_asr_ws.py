# # Websocket streaming ASR: partial transcripts while audio arrives
#
# TPU-native counterpart of the reference's streaming speech-to-text tier
# (06_gpu_and_ml/speech-to-text/streaming_kyutai_stt.py — a fastapi
# websocket endpoint streaming partial transcripts from browser
# microphones; streaming_parakeet.py; cache_aware_buffer.py — buffered
# incremental decoding). Here the whole stack is the framework's own:
#
# - `@mtpu.websocket_endpoint()` — the stdlib gateway speaks RFC 6455
#   itself (fastapi/uvicorn are optional in this image);
# - `serving.streaming_asr.StreamingTranscriber` — windowed incremental
#   Whisper with LocalAgreement-2 stabilization: stable text is committed
#   only once two consecutive updates agree on it, so committed text never
#   retracts;
# - the model is `models.whisper` (the same one the fine-tune and batched
#   examples use).
#
# Protocol (the streaming_kyutai_stt.py shape): the client streams binary
# float32 PCM chunks (16 kHz mono); the server answers with JSON events
# {"type": "partial" | "final", ...}; the text message "end" flushes.
#
# Run: tpurun run examples/06_gpu_and_ml/speech-to-text/streaming_asr_ws.py

import json
import os
import time

import modal_examples_tpu as mtpu

TPU = os.environ.get("MTPU_TPU", "") or None

app = mtpu.App("example-streaming-asr")

SR = 16000


def _make_transcriber():
    """Cheap-mode model: test-tiny whisper, random weights (the
    dummy-weights dev pattern); swap load_hf_weights for real ones."""
    import jax

    if not TPU:
        # this endpoint runs inside the gateway process, not a container;
        # in cheap mode that process stays off the chip, which belongs to
        # whichever tpu= container holds the lease
        jax.config.update("jax_platforms", "cpu")

    from modal_examples_tpu.models import whisper
    from modal_examples_tpu.serving.streaming_asr import StreamingTranscriber

    cfg = whisper.WhisperConfig.test_tiny()
    params = whisper.init_params(jax.random.PRNGKey(0), cfg)
    return StreamingTranscriber(
        params, cfg, bos_id=0, eos_id=1, sample_rate=SR,
        window_s=2.0, hop_s=0.5, max_tokens=16,
        decode_text=lambda toks: "".join(chr(97 + t % 26) for t in toks),
    )


@app.function()
@mtpu.websocket_endpoint()
def transcribe_ws(ws):
    """One connection = one stream: binary frames are PCM chunks, the text
    frame "end" finalizes. Emits {"type": "partial"} per update and one
    {"type": "final"} with the full committed transcript."""
    import numpy as np

    from modal_examples_tpu.web.websocket import ConnectionClosed

    t = _make_transcriber()
    try:
        while True:
            kind, payload = ws.receive()
            if kind == "text" and payload == b"end":
                res = t.flush()
                ws.send_json({
                    "type": "final", "text": res.committed_text,
                })
                return
            if kind == "binary":
                pcm = np.frombuffer(payload, np.float32)
                res = t.feed(pcm)
                if res is not None:
                    ws.send_json({
                        "type": "partial",
                        "stable": res.stable_text,
                        "pending": res.partial_text,
                        "committed": res.committed_text,
                    })
    except ConnectionClosed:
        pass


@app.local_entrypoint()
def main(seconds: float = 3.0, chunk_ms: int = 250):
    import numpy as np

    from modal_examples_tpu.utils.audio import synth_tone_audio
    from modal_examples_tpu.web.gateway import Gateway
    from modal_examples_tpu.web.websocket import connect

    with app.run():
        gw = Gateway(app).start()
        host, port = gw.httpd.server_address[:2]
        ws = connect(host, port, "/transcribe_ws")

        audio = synth_tone_audio([440.0, 660.0], seconds)
        chunk = int(SR * chunk_ms / 1000)
        hop = int(SR * 0.5)  # the server's update cadence (hop_s=0.5)
        partials = 0
        lat_ms = []
        got_updates = 0
        for i in range(0, len(audio), chunk):
            ws.send_bytes(audio[i : i + chunk].astype(np.float32).tobytes())
            # the server emits one event per full hop of audio, but at most
            # one per feed() call — drain exactly what is due so neither
            # side ever blocks on the other, for ANY chunk_ms
            chunks_sent = i // chunk + 1
            due = min(chunks_sent, (i + chunk) // hop)
            while got_updates < due:
                t0 = time.time()
                kind, payload = ws.receive()
                lat_ms.append((time.time() - t0) * 1e3)
                evt = json.loads(payload)
                assert evt["type"] == "partial"
                got_updates += 1
                partials += 1
                print(f"partial: committed={evt['committed']!r} "
                      f"pending={evt['pending']!r}")
        ws.send_text("end")
        while True:
            kind, payload = ws.receive()
            evt = json.loads(payload)
            if evt["type"] == "final":
                break
        ws.close()
        gw.stop()
        print(f"final transcript: {evt['text']!r}")
        print(f"partial events: {partials}, "
              f"median update latency {sorted(lat_ms)[len(lat_ms)//2]:.0f} ms")
        assert partials >= 2 and evt["text"]
