"""Tokenizers for the serving elements.

Zero-egress environment: no downloaded vocabularies.  ``ByteTokenizer`` is
the dependency-free default (byte-level, 256 + specials) -- enough for the
serving/benchmark path and tests.  ``load_tokenizer`` upgrades to a local
HuggingFace tokenizer directory when one is available (transformers is in
the image), so real Llama checkpoints drop in without code changes.
"""

from __future__ import annotations

import os

__all__ = ["ByteTokenizer", "load_tokenizer"]

_UNKNOWN = "\ufffd".encode("utf-8")


class ByteTokenizer:
    """Byte-level: token = byte value; specials above 255."""

    PAD = 256
    BOS = 257
    EOS = 258

    vocab_size = 512       # leave headroom so tiny models align

    def encode(self, text: str, add_bos: bool = True) -> list[int]:
        tokens = list(text.encode("utf-8"))
        return ([self.BOS] + tokens) if add_bos else tokens

    def decode(self, tokens) -> str:
        """Bytes decode; the specials vanish; an id PAST the specials
        (a model whose vocabulary is wider than this tokenizer's -- a
        llama3 config at 128,256 serving random weights) renders as
        U+FFFD instead of vanishing too, so generated text is never
        silently empty."""
        data = bytearray()
        for token in tokens:
            token = int(token)
            if 0 <= token < 256:
                data.append(token)
            elif token > self.EOS:
                data += _UNKNOWN
        return data.decode("utf-8", errors="replace")

    @property
    def eos_tokens(self) -> tuple:
        return (self.EOS,)


class _HFTokenizer:
    def __init__(self, tokenizer):
        self._tok = tokenizer
        self.vocab_size = tokenizer.vocab_size

    def encode(self, text: str, add_bos: bool = True) -> list[int]:
        return self._tok.encode(text, add_special_tokens=add_bos)

    def decode(self, tokens) -> str:
        return self._tok.decode(list(map(int, tokens)),
                                skip_special_tokens=True)

    @property
    def eos_tokens(self) -> tuple:
        eos = self._tok.eos_token_id
        return (eos,) if eos is not None else ()


def load_tokenizer(path: str | None = None):
    """Local tokenizer directory/file -> HF tokenizer; else bytes."""
    if path and os.path.exists(path):
        try:
            from transformers import AutoTokenizer
            return _HFTokenizer(AutoTokenizer.from_pretrained(path))
        except Exception:
            pass
    return ByteTokenizer()
