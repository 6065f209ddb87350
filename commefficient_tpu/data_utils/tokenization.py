"""Tokenizer provider for the GPT-2 workload.

The reference uses pytorch_transformers' GPT2Tokenizer downloaded from the
hub (reference gpt2_train.py:262-273). In this zero-egress environment a real
BPE vocab may not exist locally, so:

- ``get_tokenizer`` first tries ``transformers.GPT2Tokenizer`` from a local
  path/cache;
- otherwise falls back to ``ByteTokenizer`` — a byte-level vocabulary
  (ids 0..255) with the same special-token API surface. Training remains
  meaningful (same pipeline mechanics, smaller vocab).

The API subset both provide matches the calls the workload makes: special
token management (ATTR_TO_SPECIAL_TOKEN surgery, reference
gpt2_train.py:26-32, 101-111), ``tokenize``/``convert_tokens_to_ids``,
``__len__``, ``save_pretrained``.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List

SPECIAL_TOKENS = ["<bos>", "<eos>", "<speaker1>", "<speaker2>", "<pad>"]
ATTR_TO_SPECIAL_TOKEN = {
    "bos_token": "<bos>",
    "eos_token": "<eos>",
    "pad_token": "<pad>",
    "additional_special_tokens": ("<speaker1>", "<speaker2>"),
}


class ByteTokenizer:
    """Byte-level fallback tokenizer with GPT2Tokenizer-compatible surface."""

    def __init__(self):
        self.encoder: Dict[str, int] = {chr(i): i for i in range(256)}
        self.special: Dict[str, int] = {}

    def __len__(self):
        return 256 + len(self.special)

    def add_special_tokens(self, attr_to_token) -> int:
        added = 0
        for v in attr_to_token.values():
            toks = v if isinstance(v, (tuple, list)) else [v]
            for t in toks:
                if t not in self.special:
                    self.special[t] = 256 + len(self.special)
                    added += 1
        return added

    def tokenize(self, text: str) -> List[str]:
        return [chr(b) for b in text.encode("utf-8", errors="replace")]

    def convert_tokens_to_ids(self, tokens):
        if isinstance(tokens, str):
            tokens = [tokens]
            single = True
        else:
            single = False
        ids = [self.special[t] if t in self.special else
               (ord(t) % 256 if len(t) == 1 else 0) for t in tokens]
        return ids[0] if single else ids

    def encode(self, text: str):
        return self.convert_tokens_to_ids(self.tokenize(text))

    def save_pretrained(self, path: str):
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "byte_tokenizer.json"), "w") as f:
            json.dump({"special": self.special}, f)

    @classmethod
    def from_pretrained(cls, path: str):
        tok = cls()
        fn = os.path.join(path, "byte_tokenizer.json")
        if os.path.exists(fn):
            with open(fn) as f:
                tok.special = json.load(f)["special"]
        return tok


class WordTokenizer(ByteTokenizer):
    """One id a whitespace-separated word ``w<i>`` (the sized synthetic
    PersonaChat's words, data_utils/fed_persona.py), inside a vocabulary of
    ``rows`` rows whose last rows are the special tokens: ids that fill a
    slice of a large model's vocabulary. Selected by
    ``COMMEFFICIENT_WORD_VOCAB=<rows>``."""

    def __init__(self, rows: int):
        super().__init__()
        self.rows = int(rows)

    def __len__(self):
        return self.rows

    def add_special_tokens(self, attr_to_token) -> int:
        added = super().add_special_tokens(attr_to_token)
        # the special tokens in the last rows, in the order they were added
        first = self.rows - len(self.special)
        self.special = {t: first + i for i, t in enumerate(self.special)}
        return added

    def tokenize(self, text: str) -> List[str]:
        return text.split()

    def convert_tokens_to_ids(self, tokens):
        if isinstance(tokens, str):
            return self.convert_tokens_to_ids([tokens])[0]
        return [self.special[t] if t in self.special else int(t[1:])
                for t in tokens]

    def save_pretrained(self, path: str):
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "word_tokenizer.json"), "w") as f:
            json.dump({"rows": self.rows, "special": self.special}, f)


# Vendored byte-level BPE (the 256-token GPT-2 bytes->unicode alphabet,
# no merges) so the default in-image path runs the reference's real
# GPT2Tokenizer machinery (reference gpt2_train.py:262-273) instead of the
# ByteTokenizer shim. Generated from
# transformers.models.gpt2.tokenization_gpt2.bytes_to_unicode — the same
# construction tests/test_gpt2_pretrained.py proves against the HF stack.
VENDORED_BPE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "assets", "gpt2_bpe")


def get_tokenizer(model_checkpoint: str = "gpt2"):
    """HF GPT2Tokenizer from the checkpoint when available locally, else
    from the vendored byte-level BPE; ByteTokenizer as a last resort. With
    ``COMMEFFICIENT_WORD_VOCAB`` set, the ``WordTokenizer`` of that many
    rows."""
    if os.environ.get("COMMEFFICIENT_WORD_VOCAB"):
        return WordTokenizer(int(os.environ["COMMEFFICIENT_WORD_VOCAB"]))
    try:
        from transformers import GPT2Tokenizer
    except Exception:
        GPT2Tokenizer = None
    if GPT2Tokenizer is not None:
        try:
            return GPT2Tokenizer.from_pretrained(model_checkpoint,
                                                 local_files_only=True)
        except Exception:
            pass
    if os.path.isdir(model_checkpoint) and os.path.exists(
            os.path.join(model_checkpoint, "byte_tokenizer.json")):
        # a run dir saved by a ByteTokenizer round: keep the round trip
        return ByteTokenizer.from_pretrained(model_checkpoint)
    if GPT2Tokenizer is not None:
        try:
            return GPT2Tokenizer.from_pretrained(VENDORED_BPE_DIR,
                                                 local_files_only=True)
        except Exception:
            pass
    if os.path.isdir(model_checkpoint):
        return ByteTokenizer.from_pretrained(model_checkpoint)
    return ByteTokenizer()
