# # Batched Whisper transcription
#
# TPU-native counterpart of the reference's
# 06_gpu_and_ml/speech-to-text/batched_whisper.py: a transcription service
# whose `@mtpu.batched(max_batch_size=...)` method coalesces concurrent
# single-clip requests into one fixed-shape TPU batch (:127), behind an
# `@app.cls` with `@enter` model load.
#
# Run: tpurun run examples/06_gpu_and_ml/speech-to-text/batched_whisper.py

import os

import modal_examples_tpu as mtpu

TPU = os.environ.get("MTPU_TPU", "") or None
MEL_FRAMES = 200
MAX_BATCH = 8

app = mtpu.App("example-batched-whisper")


@app.cls(tpu=TPU, timeout=900, scaledown_window=300)
@mtpu.concurrent(max_inputs=MAX_BATCH)
class WhisperTranscriber:
    @mtpu.enter()
    def load(self):
        import dataclasses

        import jax
        import numpy as np

        from modal_examples_tpu.models import whisper

        self.cfg = dataclasses.replace(
            whisper.WhisperConfig.test_tiny(), vocab_size=16, n_text_ctx=8
        )
        # random weights in dev mode; point a CheckpointManager at a Volume
        # with fine_tune_asr.py's output for a trained model
        self.params = whisper.init_params(jax.random.PRNGKey(0), self.cfg)
        self.whisper = whisper
        self._transcribe = jax.jit(
            lambda p, m: whisper.greedy_transcribe(
                p, m, self.cfg, bos_id=0, eos_id=1
            )
        )
        # warm the fixed batch shape
        jax.block_until_ready(self._transcribe(
            self.params, np.zeros((MAX_BATCH, MEL_FRAMES, 80), np.float32)
        ))

    @mtpu.batched(max_batch_size=MAX_BATCH, wait_ms=100)
    @mtpu.method()
    def transcribe(self, audios: list) -> list[str]:
        """Each input is one waveform; the scheduler batches them."""
        import numpy as np

        from modal_examples_tpu.utils.audio import log_mel_spectrogram

        mels = []
        for audio in audios:
            mel = log_mel_spectrogram(np.asarray(audio), pad_to_chunk=False)
            mel = np.pad(
                mel[:MEL_FRAMES],
                ((0, MEL_FRAMES - min(len(mel), MEL_FRAMES)), (0, 0)),
            )
            mels.append(mel)
        batch = np.stack(mels)
        pad_to = MAX_BATCH  # fixed compiled shape: pad the batch dim
        if len(batch) < pad_to:
            batch = np.pad(batch, ((0, pad_to - len(batch)), (0, 0), (0, 0)))
        out = np.asarray(self._transcribe(self.params, batch))[: len(audios)]
        return [" ".join(str(t) for t in row if t != 1) for row in out]


@app.local_entrypoint()
def main(n_clips: int = 6):
    from modal_examples_tpu.utils.audio import synth_tone_audio

    clips = [
        synth_tone_audio([440.0 * (1 + i % 3)], 1.0).tolist() for i in range(n_clips)
    ]
    t = WhisperTranscriber()
    # .map fans the clips out; the @batched method coalesces them server-side
    results = list(t.transcribe.map(clips))
    for i, r in enumerate(results):
        print(f"clip {i}: tokens [{r}]")
    assert len(results) == n_clips
    print("batched transcription OK")
