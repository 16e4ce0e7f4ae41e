"""Grammar token-byte images for REAL HF tokenizer families + parser
robustness + dead-end pruning (r2 advisor findings).

The byte image of every vocab id must be the token's exact contribution to
the emitted text. Per-id ``decode([i])`` gets this wrong on the two
dominant families — SentencePiece/Metaspace strips word-leading spaces,
byte-level BPE mangles partial UTF-8 into U+FFFD — so the images are
derived from the raw vocab pieces instead (grammar.token_byte_images).
These tests build real `tokenizers`-backed HF tokenizers in-memory (no
hub access) and check the recovered bytes.
"""

from __future__ import annotations

import pytest

from production_stack_tpu.engine.grammar import (
    RegexError,
    build_token_fsm,
    compile_regex,
    token_byte_images,
)
from production_stack_tpu.engine.tokenizer import (
    ByteTokenizer,
    TransformersAuto,
)


def _wrap(hf):
    """The engine's wrapper of a transformers tokenizer object."""
    return TransformersAuto(hf)


@pytest.fixture(scope="module")
def byte_level_tok():
    """GPT-2/Llama-3 style byte-level BPE, built offline."""
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers
    from transformers import PreTrainedTokenizerFast

    vocab = {
        "<|end|>": 0,
        "Ġhello": 1,   # " hello"
        "hello": 2,
        "Ċ": 3,        # "\n"
        "é": 4,        # byte-alphabet char for the single byte 0xE9
        "Ã©": 5,       # the actual UTF-8 bytes of é
        "a": 6,
        "Ġ": 7,        # " "
    }
    tok = Tokenizer(models.BPE(vocab=vocab, merges=[], unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    return PreTrainedTokenizerFast(tokenizer_object=tok,
                                   eos_token="<|end|>")


@pytest.fixture(scope="module")
def metaspace_tok():
    """SentencePiece/Metaspace style (Llama-1/2, Mistral, Gemma)."""
    from tokenizers import Tokenizer, models
    from transformers import PreTrainedTokenizerFast

    vocab = {
        "<unk>": 0,
        "<s>": 1,
        "</s>": 2,
        "▁Hello": 3,
        "Hello": 4,
        "▁": 5,
        "<0x0A>": 6,   # byte-fallback newline
        "lo": 7,
    }
    tok = Tokenizer(models.WordLevel(vocab=vocab, unk_token="<unk>"))
    return PreTrainedTokenizerFast(
        tokenizer_object=tok, unk_token="<unk>", bos_token="<s>",
        eos_token="</s>",
    )


def test_byte_level_images(byte_level_tok):
    imgs = token_byte_images(_wrap(byte_level_tok), 8)
    assert imgs[0] == b""            # special
    assert imgs[1] == b" hello"      # Ġ → space, NOT stripped
    assert imgs[2] == b"hello"
    assert imgs[3] == b"\n"
    assert imgs[5] == b"\xc3\xa9"    # exact UTF-8 bytes of é
    assert imgs[7] == b" "


def test_byte_level_partial_utf8_not_mangled(byte_level_tok):
    """'é' the PIECE is the byte-alphabet char for the lone byte 0xE9 —
    not valid UTF-8 by itself. decode() would return U+FFFD; the image
    must be the raw byte."""
    imgs = token_byte_images(_wrap(byte_level_tok), 8)
    assert imgs[4] == b"\xe9"


def test_metaspace_images(metaspace_tok):
    imgs = token_byte_images(_wrap(metaspace_tok), 8)
    assert imgs[3] == b" Hello"      # ▁ → space, the advisor's case
    assert imgs[4] == b"Hello"
    assert imgs[5] == b" "
    assert imgs[6] == b"\n"          # <0x0A> byte fallback
    assert imgs[7] == b"lo"
    for sid in (0, 1, 2):            # specials
        assert imgs[sid] == b""


def test_padded_vocab_ids_get_empty_images(metaspace_tok):
    """ids in [len(tokenizer), config.vocab_size) — padded model vocabs
    (e.g. phi-3 32064 vs 32011) — must yield b'' instead of raising."""
    imgs = token_byte_images(_wrap(metaspace_tok), 12)
    assert len(imgs) == 12
    assert all(b == b"" for b in imgs[8:])


def test_metaspace_leading_space_token_admitted(metaspace_tok):
    """The FSM must accept '▁Hello' where the grammar expects ' Hello' —
    with decode()-based images the leading space was lost and guided
    output could violate the grammar on SP models."""
    imgs = token_byte_images(_wrap(metaspace_tok), 8)
    dfa = compile_regex(r" Hello")
    fsm = build_token_fsm(dfa, imgs)
    nxt = fsm.trans[0, 3]  # ▁Hello from the start state
    assert nxt >= 0 and fsm.accept[nxt]


def test_added_special_token_outside_all_special_ids_gets_empty_image():
    """Added tokens flagged special=True (Llama-3-style <|reserved_...|>
    control tokens) are dropped by decode(skip_special_tokens=True) even
    when they never make it into all_special_ids — a literal byte image
    would advance the FSM with text that never appears (r3 advisor)."""
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers
    from transformers import AddedToken, PreTrainedTokenizerFast

    vocab = {"<|end|>": 0, "a": 1, "b": 2}
    tok = Tokenizer(models.BPE(vocab=vocab, merges=[], unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    hf = PreTrainedTokenizerFast(tokenizer_object=tok, eos_token="<|end|>")
    hf.add_tokens([AddedToken("<|reserved_0|>", special=True)])
    hf.add_tokens([AddedToken("<|tool|>", special=False)])
    rid = hf.convert_tokens_to_ids("<|reserved_0|>")
    tid = hf.convert_tokens_to_ids("<|tool|>")
    assert rid not in set(hf.all_special_ids)  # the advisor's precondition
    imgs = token_byte_images(_wrap(hf), len(hf))
    assert imgs[rid] == b""                    # dropped from decoded text
    assert imgs[tid] == b"<|tool|>"            # non-special stays literal


def test_byte_tokenizer_images_exact():
    imgs = token_byte_images(ByteTokenizer(), 259)
    assert imgs[0x41] == b"A"
    assert imgs[0x80] == b"\x80"     # decode() would give U+FFFD bytes
    assert imgs[256] == imgs[257] == imgs[258] == b""


# -- parser robustness (r2 advisor, low) ------------------------------------


@pytest.mark.parametrize("pat", [
    "abc\\",        # bare trailing backslash: was IndexError → 500
    "a{2",          # unbalanced brace
    "a{x}",         # non-numeric counts
    r"\x4",         # truncated hex escape
    r"ab\x",        # \x with nothing after
    r"[a\ ",        # truncated escape inside a class...
])
def test_malformed_patterns_raise_regex_error(pat):
    with pytest.raises(RegexError):
        compile_regex(pat)


# -- token-level dead-end pruning (r2 advisor, low) --------------------------


def test_dead_end_edges_pruned():
    """Pattern ab|cd with a vocab that has no 'b': the 'a' branch is a
    token-level trap (non-accepting state, no admissible token) and must
    be pruned so sampling can never enter it."""
    dfa = compile_regex("ab|cd")
    toks = [b"a", b"cd", b"c", b"d"]
    fsm = build_token_fsm(dfa, toks)
    assert fsm.trans[0, 0] == -1          # 'a' edge cut
    assert fsm.trans[0, 1] >= 0           # 'cd' still fine
    s_c = fsm.trans[0, 2]
    assert s_c >= 0 and fsm.trans[s_c, 3] >= 0  # 'c' then 'd'


def test_unsatisfiable_grammar_rejected_at_build():
    dfa = compile_regex("ab")
    with pytest.raises(RegexError, match="no token sequence"):
        build_token_fsm(dfa, [b"a", b"x"])


def test_pruning_keeps_multi_token_paths():
    dfa = compile_regex("abc")
    fsm = build_token_fsm(dfa, [b"a", b"bc", b"abc"])
    assert fsm.trans[0, 0] >= 0
    assert fsm.trans[0, 2] >= 0
    nxt = fsm.trans[0, 0]
    end = fsm.trans[nxt, 1]
    assert end >= 0 and fsm.accept[end]
