"""Tokenizers: HF wrapper (local files) + a dependency-free byte tokenizer.

The byte tokenizer exists so every test, CI run and synthetic benchmark works
in a zero-egress environment (no HF hub): ids 0..255 are raw bytes, then
bos/eos/pad. Any model config with vocab_size >= 259 can serve under it.
"""

from __future__ import annotations

import logging
from typing import Callable, Optional, Protocol, Sequence


class Tokenizer(Protocol):
    bos_id: Optional[int]
    eos_id: Optional[int]

    def encode(self, text: str, add_bos: bool = True) -> list[int]: ...
    def decode(self, ids: Sequence[int]) -> str: ...
    def stream_decoder(self) -> Callable[[Sequence[int]], str]: ...
    @property
    def vocab_size(self) -> int: ...


class ByteTokenizer:
    def __init__(self):
        self.bos_id = 256
        self.eos_id = 257
        self.pad_id = 258

    @property
    def vocab_size(self) -> int:
        return 259

    def encode(self, text: str, add_bos: bool = True) -> list[int]:
        ids = list(text.encode("utf-8"))
        return ([self.bos_id] if add_bos else []) + ids

    def decode(self, ids: Sequence[int]) -> str:
        return bytes(i for i in ids if 0 <= i < 256).decode("utf-8", errors="replace")

    def stream_decoder(self) -> Callable[[Sequence[int]], str]:
        # a thousand bytes decode in microseconds: the whole list each time
        return self.decode


class HFTokenizer:
    """transformers AutoTokenizer over a *local* path (PVC-mounted weights
    dir, as the reference mounts model PVCs — SURVEY.md §5.4)."""

    def __init__(self, path: str):
        from transformers import AutoTokenizer

        self.tk = AutoTokenizer.from_pretrained(path, local_files_only=True)
        self.bos_id = self.tk.bos_token_id
        self.eos_id = self.tk.eos_token_id

    @property
    def vocab_size(self) -> int:
        return len(self.tk)

    def encode(self, text: str, add_bos: bool = True) -> list[int]:
        ids = self.tk.encode(text, add_special_tokens=False)
        if add_bos and self.bos_id is not None:
            ids = [self.bos_id] + ids
        return ids

    def decode(self, ids: Sequence[int]) -> str:
        return self.tk.decode(ids, skip_special_tokens=True)

    def stream_decoder(self) -> Callable[[Sequence[int]], str]:
        return WindowedDecoder(self.decode)


class WindowedDecoder:
    """The text of ONE growing token list, decoded a window at a time:
    ``decoder(ids)`` returns what ``decode(ids)`` would, for the cost of
    two decodes of the last few tokens instead of one of the whole list
    (an HF decode is ~10 us + 0.25 us a token: a stream of 768 tokens paid
    200 us a token at its end, on the server's one event-loop thread).

    The window is the tokens since the last text taken plus the ones
    before them that decided how those rendered (a leading space, a
    merged byte sequence): the new tokens' text is what the window reads
    beyond what its head read alone. Text that ends in U+FFFD is an
    unfinished multi-byte character and is held back until the tokens
    that complete it arrive; the caller flushes the tail with one whole
    ``decode`` at the stream's end."""

    def __init__(self, decode: Callable[[Sequence[int]], str]):
        self.decode = decode
        self.text = ""
        self.head = self.read = 0  # ids[head:read]: the window's old part

    def __call__(self, ids: Sequence[int]) -> str:
        old = self.decode(ids[self.head:self.read])
        new = self.decode(ids[self.head:])
        if len(new) > len(old) and not new.endswith("\ufffd"):
            self.text += new[len(old):]
            self.head, self.read = self.read, len(ids)
        return self.text


def get_tokenizer(path: Optional[str]) -> Tokenizer:
    if path:
        try:
            return HFTokenizer(path)
        except Exception:
            logging.getLogger(__name__).warning(
                "failed to load HF tokenizer from %r; falling back to "
                "the byte tokenizer (served text will be raw bytes)",
                path, exc_info=True)
    return ByteTokenizer()
