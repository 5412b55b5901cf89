"""A vocabulary-complete tokenizer for seeded-weight serving runs.

There is no network and no checkpoint here, so the engine would fall back to
its byte tokenizer: 259 ids of a 32000-row vocabulary, and a generated id
above 255 decodes to no text at all, so a client would see almost no stream.
This tokenizer stands where the model's own (HF) tokenizer stands in a
deployment: every id of the vocabulary is a fixed three-letter word, so

- the client writes a prompt of exactly the token ids the seed drew, over
  the whole vocabulary, as plain text through the OpenAI API;
- every generated token reaches the client as three characters, so the
  client counts tokens, times them, and reads back the served ids for the
  comparison with the reference.

Special ids follow Mistral's tokenizer (unk/pad 0, bos 1; the load generator
never draws ids below 3 for a prompt), except that the end-of-sequence id is
one the model cannot produce. The API has no ``ignore_eos``, a mix fixes its
output lengths through ``max_tokens``, and with seeded weights an EOS would
be a 1-in-32000 accident per token that changes a run's work. Jax-free: both
the serving container and the load generator import it.
"""

from __future__ import annotations

ALPHABET = "abcdefghijklmnopqrstuvwxyz234567"  # 32 symbols, 3 per id: 32768
WORD = 3
PAD_ID, BOS_ID = 0, 1
FIRST_PLAIN_ID = 3
_INDEX = {c: i for i, c in enumerate(ALPHABET)}


def word_of(token_id: int) -> str:
    a, rest = divmod(int(token_id), 1024)
    b, c = divmod(rest, 32)
    return ALPHABET[a] + ALPHABET[b] + ALPHABET[c]


def text_of(ids) -> str:
    return "".join(word_of(i) for i in ids)


def ids_of(text: str) -> list[int]:
    """Inverse of :func:`text_of`; characters outside the alphabet (the
    chat template's separators, a stray partial word) are dropped."""
    digits = [_INDEX[c] for c in text if c in _INDEX]
    n = len(digits) - len(digits) % WORD
    return [
        digits[i] * 1024 + digits[i + 1] * 32 + digits[i + 2]
        for i in range(0, n, WORD)
    ]


class IdTokenizer:
    """The engine-side face: the methods ``LLMEngine`` and the OpenAI front
    call on a tokenizer (``utils/tokenizer.py`` is the program's pair)."""

    def __init__(self, vocab_size: int):
        if not FIRST_PLAIN_ID < vocab_size <= len(ALPHABET) ** WORD:
            raise ValueError(f"vocab_size {vocab_size} does not fit 3 letters")
        self.vocab_size = vocab_size
        self.pad_id, self.bos_id = PAD_ID, BOS_ID
        self.eos_id = vocab_size  # outside the vocabulary: never sampled

    def encode(self, text: str, add_bos: bool = True) -> list[int]:
        ids = [i % self.vocab_size for i in ids_of(text)]
        return ([self.bos_id] + ids) if add_bos else ids

    def decode(self, ids) -> str:
        return text_of(ids)

    def apply_chat_template(self, messages: list[dict], **_) -> str:
        return "".join(str(m.get("content", "")) for m in messages)
