"""Tokenizers: a model directory's own (local files, read through the
`tokenizers` library, or through `transformers` where only it can) + a
dependency-free byte tokenizer.

The byte tokenizer exists so every test, CI run and synthetic benchmark works
in a zero-egress environment (no HF hub): ids 0..255 are raw bytes, then
bos/eos/pad. Any model config with vocab_size >= 259 can serve under it.
"""

from __future__ import annotations

import glob
import json
import logging
import os
from typing import Callable, Optional, Protocol, Sequence

logger = logging.getLogger(__name__)


class Tokenizer(Protocol):
    bos_id: Optional[int]
    eos_id: Optional[int]
    loader: str  # what read it: "tokenizers", "transformers" or "bytes"

    def encode(self, text: str, add_bos: bool = True) -> list[int]: ...
    def decode(self, ids: Sequence[int]) -> str: ...
    def stream_decoder(self) -> Callable[[Sequence[int]], str]: ...
    def render_chat(self, messages: list[dict]) -> Optional[str]: ...
    @property
    def vocab_size(self) -> int: ...


class ByteTokenizer:
    loader = "bytes"

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

    def render_chat(self, messages: list[dict]) -> Optional[str]:
        return None  # no template: the server's plain role headers


class HFTokenizer:
    """The tokenizer of a *local* model directory (PVC-mounted weights dir,
    as the reference mounts model PVCs — SURVEY.md §5.4): what the engine,
    the server and the grammar compiler ask of one, whichever library read
    the files. `TokenizersFile` and `TransformersAuto` answer it;
    `load_tokenizer_dir` chooses between them by what the directory
    holds."""

    loader = ""
    bos_id: Optional[int]
    eos_id: Optional[int]

    def encode(self, text: str, add_bos: bool = True) -> list[int]:
        ids = self._encode(text)
        if add_bos and self.bos_id is not None:
            ids = [self.bos_id] + ids
        return ids

    def stream_decoder(self) -> Callable[[Sequence[int]], str]:
        return WindowedDecoder(self.decode)

    def render_chat(self, messages: list[dict]) -> Optional[str]:
        """``messages`` through the directory's chat template, with the
        generation prompt; None where the directory has no template."""
        raise NotImplementedError

    def vocab_pieces(self) -> tuple[list, dict, set]:
        """The raw vocabulary, for `grammar.token_byte_images`: the piece
        of every id below `vocab_size` as stored (None where an id has
        none), the added tokens' literal contents by id, and the ids
        `decode` leaves out (the special tokens)."""
        raise NotImplementedError


class TransformersAuto(HFTokenizer):
    """A `transformers` tokenizer object: the only reader of a directory
    with a SentencePiece `.model` or a tiktoken file alone. Importing the
    library is 6-11 s of a start (17-21 s on the benchmark's host:
    PERF.md section 6, PR 54), so `load` is what imports it."""

    loader = "transformers"

    def __init__(self, tk):
        self.tk = tk
        self.bos_id = tk.bos_token_id
        self.eos_id = tk.eos_token_id

    @classmethod
    def load(cls, path: str) -> "TransformersAuto":
        from transformers import AutoTokenizer

        return cls(AutoTokenizer.from_pretrained(path, local_files_only=True))

    @property
    def vocab_size(self) -> int:
        return len(self.tk)

    def _encode(self, text: str) -> list[int]:
        return self.tk.encode(text, add_special_tokens=False)

    def decode(self, ids: Sequence[int]) -> str:
        return self.tk.decode(ids, skip_special_tokens=True)

    def render_chat(self, messages: list[dict]) -> Optional[str]:
        if not getattr(self.tk, "chat_template", None):
            return None
        return self.tk.apply_chat_template(
            messages, tokenize=False, add_generation_prompt=True)

    def vocab_pieces(self) -> tuple[list, dict, set]:
        tk = self.tk
        special = set(getattr(tk, "all_special_ids", None) or [])
        added = {}
        for i, t in (getattr(tk, "added_tokens_decoder", None) or {}).items():
            added[int(i)] = getattr(t, "content", str(t))
            # tokens flagged special=True in added_tokens_decoder
            # (Llama-3-style <|reserved_...|> control tokens) are dropped by
            # decode(skip_special_tokens=True) even when they're missing
            # from all_special_ids
            if getattr(t, "special", False):
                special.add(int(i))
        return tk.convert_ids_to_tokens(list(range(len(tk)))), added, special


# The names under which a tokenizer's files give one special token each, in
# the order transformers lists (and so adds) them; after them come
# `additional_special_tokens` and a model's own (`extra_special_tokens`).
SPECIAL_TOKEN_NAMES = ("bos_token", "eos_token", "unk_token", "sep_token",
                       "pad_token", "cls_token", "mask_token")


def _read_json(path: str, name: str):
    """The JSON file ``name`` of the directory ``path``, None if absent."""
    try:
        with open(os.path.join(path, name), encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        return None


def _added_token(value, **more):
    """A token as a tokenizer's files spell one: a string, or the fields of
    an `AddedToken` (tokenizer_config.json marks those with ``__type``)."""
    if not isinstance(value, dict):
        return value
    from tokenizers import AddedToken

    fields = {k: v for k, v in value.items() if k != "__type"}
    return AddedToken(**{**fields, **more})


def _init_kwargs(path: str, tk) -> dict:
    """What transformers 4.57.6's `_from_pretrained` hands a fast
    tokenizer's ``__init__`` for the directory ``path`` (``tk``: its
    tokenizer.json, loaded): tokenizer_config.json, the chat template
    files, which win over the config's entry, and, in a directory saved
    before the config listed `added_tokens_decoder`,
    special_tokens_map.json, whose entries win over the config's. Special
    tokens come back as strings or `AddedToken`s, one of the added tokens'
    own where the contents match."""
    config = _read_json(path, "tokenizer_config.json") or {}
    files = {"default": os.path.join(path, "chat_template.jinja")}
    for file in sorted(glob.glob(os.path.join(
            path, "additional_chat_templates", "*.jinja"))):
        files[os.path.basename(file)[:-len(".jinja")]] = file
    templates = {}
    for name, file in files.items():
        if os.path.isfile(file):
            with open(file, encoding="utf-8") as f:
                templates[name] = f.read()
    if templates:
        config["chat_template"] = (
            templates["default"] if set(templates) == {"default"}
            else templates)
    if "added_tokens_decoder" in config:
        added = {int(i): _added_token(t)
                 for i, t in config["added_tokens_decoder"].items()}
    else:
        for name, value in (
                _read_json(path, "special_tokens_map.json") or {}).items():
            if name == "additional_special_tokens" and isinstance(value, list):
                merged = config.pop(name, None) or []
                for token in value:
                    token = _added_token(token, special=True)
                    if token not in merged:
                        merged.append(token)
                value = merged
            config[name] = _added_token(value, special=True)
        added = tk.get_added_tokens_decoder()
    config["added_tokens_decoder"] = added
    by_content = {str(t): t for t in added.values()}
    for name in SPECIAL_TOKEN_NAMES:
        if config.get(name) is not None:
            token = _added_token(config[name])
            config[name] = by_content.get(str(token), token)
    for name in ("additional_special_tokens", "extra_special_tokens"):
        held = config.get(name)
        if isinstance(held, dict):
            config[name] = {k: _added_token(t) for k, t in held.items()}
        elif held:
            config[name] = [_added_token(t) for t in held]
    return config


def clean_up_tokenization(text: str) -> str:
    """transformers' `PreTrainedTokenizerBase.clean_up_tokenization`: the
    spaces a word-level decode leaves before English punctuation and
    contractions."""
    for spaced, joined in ((" .", "."), (" ?", "?"), (" !", "!"), (" ,", ","),
                           (" ' ", "'"), (" n't", "n't"), (" 'm", "'m"),
                           (" 's", "'s"), (" 've", "'ve"), (" 're", "'re")):
        text = text.replace(spaced, joined)
    return text


def compile_chat_template(template: str):
    """``template`` compiled in the environment transformers 4.57.6 builds
    for one (utils/chat_template_utils.py `_compile_jinja_template`):
    immutable and sandboxed, blocks trimmed, loop controls,
    ``raise_exception``, ``strftime_now``, a ``tojson`` that leaves HTML
    characters alone, and ``{% generation %}`` blocks (they mark the
    assistant's text for a training mask) rendered as their body."""
    import datetime

    import jinja2
    import jinja2.ext
    import jinja2.sandbox

    class Generation(jinja2.ext.Extension):
        tags = {"generation"}

        def parse(self, parser):
            lineno = next(parser.stream).lineno
            body = parser.parse_statements(
                ["name:endgeneration"], drop_needle=True)
            return jinja2.nodes.CallBlock(
                self.call_method("_body"), [], [], body).set_lineno(lineno)

        def _body(self, caller):
            return caller()

    def raise_exception(message):
        raise jinja2.exceptions.TemplateError(message)

    def tojson(x, ensure_ascii=False, indent=None, separators=None,
               sort_keys=False):
        return json.dumps(x, ensure_ascii=ensure_ascii, indent=indent,
                          separators=separators, sort_keys=sort_keys)

    def strftime_now(format):
        return datetime.datetime.now().strftime(format)

    env = jinja2.sandbox.ImmutableSandboxedEnvironment(
        trim_blocks=True, lstrip_blocks=True,
        extensions=[Generation, jinja2.ext.loopcontrols])
    env.filters["tojson"] = tojson
    env.globals["raise_exception"] = raise_exception
    env.globals["strftime_now"] = strftime_now
    return env.from_string(template)


class TokenizersFile(HFTokenizer):
    """A directory's tokenizer.json through the `tokenizers` library alone:
    the object a `PreTrainedTokenizerFast` holds as ``_tokenizer``, set up
    from the fields of `_init_kwargs` as that class's ``__init__`` sets it
    up (transformers 4.57.6, tokenization_utils_fast.py), so that ids,
    text and chat prompts come out as `AutoTokenizer`'s do
    (tests/test_tokenizer_loaders.py holds the two side by side). Nothing
    here imports `transformers`.

    Not mirrored, because only a tokenizer class of its own does it: a
    backend rebuilt from a slow tokenizer (``from_slow``; a Llama class
    given ``add_prefix_space``), ``fix_mistral_regex``, versioned
    ``fast_tokenizer_files``, a legacy added_tokens.json."""

    loader = "tokenizers"

    def __init__(self, tk, config: dict):
        from tokenizers import AddedToken, pre_tokenizers

        self.tk = tk
        # name -> str | AddedToken (a list of them under
        # additional_special_tokens), those that are set, in transformers'
        # order
        special = {name: config[name] for name in SPECIAL_TOKEN_NAMES
                   if config.get(name)}
        if config.get("additional_special_tokens"):
            special["additional_special_tokens"] = config[
                "additional_special_tokens"]
        special.update((name, token) for name, token in (
            config.get("extra_special_tokens") or {}).items() if token)
        by_name = {}  # each special token once
        for value in special.values():
            for token in value if isinstance(value, list) else [value]:
                by_name.setdefault(str(token), token)
        names, every = list(by_name), list(by_name.values())
        # The config's added tokens, then the special tokens the file does
        # not list among its added ones (an `AddedToken` never equals a
        # string, so one of those is always added again, as special), are
        # added to the backend: a special token gets an id even where the
        # vocabulary lacks it, and `decode` leaves all of them out.
        to_add = [t for _, t in sorted(config["added_tokens_decoder"].items())]
        listed = [t.content for _, t in sorted(
            tk.get_added_tokens_decoder().items())] + [str(t) for t in to_add]
        to_add += [t for t in every if t not in listed and t not in to_add]
        for n, token in enumerate(to_add):
            is_special = str(token) in names or (
                isinstance(token, AddedToken) and token.special)
            if isinstance(token, str):
                to_add[n] = AddedToken(token, special=is_special)
            else:
                token.special = is_special
        if to_add:
            tk.add_tokens(to_add)
        prefix_space = config.get("add_prefix_space", False)
        try:
            state = json.loads(tk.pre_tokenizer.__getstate__())
            if state.get("add_prefix_space", prefix_space) != prefix_space:
                kind = getattr(pre_tokenizers, state.pop("type"))
                state["add_prefix_space"] = prefix_space
                tk.pre_tokenizer = kind(**state)
        except Exception:
            # no pre-tokenizer, or one that cannot be written out: then
            # there is none whose prefix space the config could set
            # (transformers passes over the same errors)
            logger.debug("the pre-tokenizer stays as tokenizer.json has it",
                         exc_info=True)
        # an encode never truncates or pads, whatever the file asks for
        # (`set_truncation_and_padding` under `encode`'s defaults)
        tk.no_truncation()
        tk.no_padding()
        tk.encode_special_tokens = bool(
            config.get("split_special_tokens", False))
        # 4.57.6's default where the config does not say
        self.clean_up = bool(
            config.get("clean_up_tokenization_spaces", False))
        unk = (tk.token_to_id(str(special["unk_token"]))
               if "unk_token" in special else None)

        def token_id(token) -> Optional[int]:
            found = tk.token_to_id(str(token))
            return unk if found is None else found

        self._special_ids = {token_id(t) for t in every} - {None}
        self.bos_id, self.eos_id = (
            token_id(special[name]) if name in special else None
            for name in ("bos_token", "eos_token"))
        # what a template may name besides the messages
        self._template_vars = {
            name: [str(t) for t in v] if isinstance(v, list) else str(v)
            for name, v in special.items()}
        chat_template = config.get("chat_template")
        if isinstance(chat_template, (list, tuple)):
            chat_template = {t["name"]: t["template"] for t in chat_template}
        self.chat_template = chat_template
        self._compiled = None  # at the first chat request

    @classmethod
    def load(cls, path: str) -> "TokenizersFile":
        from tokenizers import Tokenizer

        tk = Tokenizer.from_file(os.path.join(path, "tokenizer.json"))
        return cls(tk, _init_kwargs(path, tk))

    @property
    def vocab_size(self) -> int:
        return self.tk.get_vocab_size(with_added_tokens=True)

    def _encode(self, text: str) -> list[int]:
        return self.tk.encode(text, add_special_tokens=False).ids

    def decode(self, ids: Sequence[int]) -> str:
        text = self.tk.decode(ids, skip_special_tokens=True)
        return clean_up_tokenization(text) if self.clean_up else text

    def render_chat(self, messages: list[dict]) -> Optional[str]:
        template = self.chat_template
        if not template:
            return None
        if self._compiled is None:
            if isinstance(template, dict):
                if "default" not in template:
                    raise ValueError(
                        "the model directory names several chat templates "
                        f"and none is `default`: {sorted(template)}")
                template = template["default"]
            self._compiled = compile_chat_template(template)
        return self._compiled.render(
            messages=messages, tools=None, documents=None,
            add_generation_prompt=True, **self._template_vars)

    def vocab_pieces(self) -> tuple[list, dict, set]:
        added = self.tk.get_added_tokens_decoder()
        special = self._special_ids | {
            i for i, t in added.items() if t.special}
        return ([self.tk.id_to_token(i) for i in range(self.vocab_size)],
                {i: t.content for i, t in added.items()}, special)


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


def load_tokenizer_dir(path: str) -> HFTokenizer:
    """The tokenizer of the model directory ``path``, by what the
    directory holds: a tokenizer.json is read through `tokenizers`;
    anything else (a SentencePiece `.model`, a tiktoken file: only
    `transformers` can turn those into a tokenizer), or a tokenizer.json
    that `tokenizers` cannot read, goes through `AutoTokenizer` and pays
    its import."""
    if os.path.isfile(os.path.join(path, "tokenizer.json")):
        try:
            return TokenizersFile.load(path)
        except Exception:
            logger.warning(
                "cannot read %r through the tokenizers library; "
                "trying transformers' AutoTokenizer",
                os.path.join(path, "tokenizer.json"), exc_info=True)
    return TransformersAuto.load(path)


def get_tokenizer(path: Optional[str]) -> Tokenizer:
    if path:
        try:
            return load_tokenizer_dir(path)
        except Exception:
            logger.warning(
                "failed to load HF tokenizer from %r; falling back to "
                "the byte tokenizer (served text will be raw bytes)",
                path, exc_info=True)
    return ByteTokenizer()
