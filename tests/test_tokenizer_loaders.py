"""A model directory's tokenizer through the `tokenizers` library alone
(engine/tokenizer.py `TokenizersFile`) against the same directory through
`AutoTokenizer` (`TransformersAuto`): every directory is made here, nothing
is downloaded. Then which loader a directory gets, that the first never
imports `transformers`, and where the loader's name is told."""

import asyncio
import dataclasses
import json
import logging
import os
import subprocess
import sys

import pytest

from production_stack_tpu.engine import tokenizer as T
from production_stack_tpu.engine.grammar import token_byte_images

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LINES = [
    "Hello world, this is a test. Isn't it? It's fine!",
    "The quick brown fox jumps over the lazy dog , twice .",
    "naïve café — 日本語のテキスト and emoji 🙂 too",
    "  leading spaces and\ttabs\nand new lines",
    "I 'm sure they 've seen we 're here , don't you ?",
]
SPECIALS = ["<|begin|>", "<|end|>", "<|user|>", "<|assistant|>"]
TEMPLATE = (
    "{{ bos_token }}{% for m in messages %}"
    "{% if m['role'] == 'system' %}[SYS] {{ m['content'] | trim }}\n"
    "{% elif m['role'] == 'tool' %}{% continue %}"
    "{% else %}<|{{ m['role'] }}|> {{ m['content'] }}{{ eos_token }}\n"
    "{% endif %}{% endfor %}"
    "{% if add_generation_prompt %}<|assistant|>{% endif %}"
    "{% if pad_token is defined %} pad={{ pad_token }}{% endif %}"
    "{{ {'n': messages | length, 'lt': '<'} | tojson }}")
CHATS = [
    [{"role": "user", "content": "Hello there"}],
    [{"role": "system", "content": "  Be brief.  "},
     {"role": "user", "content": "What's 2 + 2?"},
     {"role": "tool", "content": "dropped"},
     {"role": "assistant", "content": "4"},
     {"role": "user", "content": "And naïve café?"}],
]


def _dump(d, name, obj):
    with open(os.path.join(d, name), "w", encoding="utf-8") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


def word_level(d, vocab=64):
    """chipbench's one-word-per-id directory (chipbench/run.py
    prepare_model_dir)."""
    _dump(d, "tokenizer.json", {
        "version": "1.0", "truncation": None, "padding": None,
        "added_tokens": [], "normalizer": None,
        "pre_tokenizer": {"type": "WhitespaceSplit"},
        "post_processor": None, "decoder": None,
        "model": {"type": "WordLevel",
                  "vocab": {f"t{i}": i for i in range(vocab)},
                  "unk_token": "t0"}})
    _dump(d, "tokenizer_config.json",
          {"tokenizer_class": "PreTrainedTokenizerFast"})


def byte_level_bpe(d, config, special_flags=True, prefix_space=False,
                   **files):
    """A small byte-level BPE trained on `LINES`, `SPECIALS` added;
    ``config`` is what tokenizer_config.json says besides its class."""
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers

    tok = Tokenizer(models.BPE())
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(
        add_prefix_space=prefix_space)
    tok.decoder = decoders.ByteLevel()
    tok.train_from_iterator(LINES * 4, trainers.BpeTrainer(
        vocab_size=420, show_progress=False,
        special_tokens=SPECIALS if special_flags else [],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet()))
    if not special_flags:
        tok.add_tokens(SPECIALS)  # added, but not flagged special
    tok.save(os.path.join(d, "tokenizer.json"))
    _dump(d, "tokenizer_config.json",
          {"tokenizer_class": "PreTrainedTokenizerFast", **config})
    for name, obj in files.items():
        os.makedirs(os.path.dirname(os.path.join(d, name)) or d, exist_ok=True)
        _dump(d, name, obj)
    return tok


def _decoder_entry(content, special=True):
    return {"content": content, "lstrip": False, "normalized": False,
            "rstrip": False, "single_word": False, "special": special}


def _typed(content):
    return {"__type": "AddedToken", **_decoder_entry(content)}


CASES = {
    "harness_word_level": lambda d: word_level(d),
    # tokens as strings, the config lists the added tokens (a directory
    # saved by a current transformers), the template in the config
    "bpe_strings_template_in_config": lambda d: byte_level_bpe(d, {
        "bos_token": "<|begin|>", "eos_token": "<|end|>",
        "added_tokens_decoder": {"0": _decoder_entry("<|begin|>"),
                                 "1": _decoder_entry("<|end|>")},
        "chat_template": TEMPLATE}),
    # tokens as AddedToken dicts, the clean-up on, a pad token the
    # vocabulary lacks (it gets an id), the template in a file of its own
    # that wins over the config's
    "bpe_added_token_dicts_clean_up_jinja_file": lambda d: byte_level_bpe(d, {
        "bos_token": _typed("<|begin|>"), "eos_token": _typed("<|end|>"),
        "pad_token": "<|pad|>", "clean_up_tokenization_spaces": True,
        "additional_special_tokens": ["<|user|>"],
        "added_tokens_decoder": {"0": _decoder_entry("<|begin|>")},
        "chat_template": "the config's, not used"},
        **{"chat_template.jinja": TEMPLATE}),
    # a directory saved before the config listed the added tokens: the
    # special tokens in special_tokens_map.json, one as a dict, and the
    # added tokens not flagged special in tokenizer.json
    "bpe_legacy_special_tokens_map": lambda d: byte_level_bpe(d, {
        "eos_token": "<|user|>", "clean_up_tokenization_spaces": False,
        "chat_template": [{"name": "default", "template": TEMPLATE},
                          {"name": "tool_use", "template": "unused"}]},
        special_flags=False,
        **{"special_tokens_map.json": {
            "bos_token": "<|begin|>",
            "eos_token": {"content": "<|end|>", "lstrip": False,
                          "normalized": False, "rstrip": False,
                          "single_word": False},
            "additional_special_tokens": ["<|assistant|>"]}}),
    # a config that names its class and no more, named templates in their
    # directory, and a prefix space in the file that the silent config
    # turns off
    "bpe_bare_config_named_templates": lambda d: byte_level_bpe(
        d, {}, prefix_space=True, **{
            "additional_chat_templates/rag.jinja": "unused",
            "chat_template.jinja": TEMPLATE}),
    # a string token that IS among the added ones, not flagged special, is
    # left as it is: decode keeps it
    "bpe_string_special_already_added": lambda d: byte_level_bpe(d, {
        "eos_token": "<|end|>", "unk_token": "<|begin|>",
        "extra_special_tokens": {"image_token": "<|image|>"},
        "added_tokens_decoder": {}}, special_flags=False),
}


@pytest.fixture(scope="module", params=sorted(CASES))
def pair(request, tmp_path_factory):
    d = str(tmp_path_factory.mktemp(request.param))
    CASES[request.param](d)
    return T.TokenizersFile.load(d), T.TransformersAuto.load(d)


TEXTS = LINES + [
    "", " ", "t5 t9  t63 zzz t1",
    "<|begin|>hi<|end|> and <|user|> mid <|assistant|><|pad|>",
    " <|end|> ", "a" * 300,
]


def test_the_two_loaders_answer_alike(pair):
    mine, ref = pair
    assert (mine.loader, ref.loader) == ("tokenizers", "transformers")
    assert (mine.bos_id, mine.eos_id) == (ref.bos_id, ref.eos_id)
    assert mine.vocab_size == ref.vocab_size
    id_lists = []
    for text in TEXTS:
        for add_bos in (True, False):
            ids = mine.encode(text, add_bos=add_bos)
            assert ids == ref.encode(text, add_bos=add_bos), text
        id_lists.append(ids)
    n = mine.vocab_size
    id_lists += [list(range(n)), list(range(n - 1, -1, -1)),
                 [i * 7 % n for i in range(200)],
                 # what the clean-up touches, id by id
                 ref.encode("we 're here , are n't we ? yes ! it 's . ' x '",
                            add_bos=True)]
    for ids in id_lists:
        assert mine.decode(ids) == ref.decode(ids)
        assert mine.decode(tuple(ids)) == ref.decode(ids)
    for ids in id_lists[:8] + id_lists[-2:]:
        a, b = mine.stream_decoder(), ref.stream_decoder()
        for k in range(1, len(ids) + 1):
            assert a(ids[:k]) == b(ids[:k])
    for chat in CHATS:
        assert mine.render_chat(chat) == ref.render_chat(chat)
    assert token_byte_images(mine, n + 3) == token_byte_images(ref, n + 3)


def test_what_each_case_is_there_to_show(tmp_path):
    """The cases above differ where they were meant to: a template was
    rendered, the clean-up ran, a token the vocabulary lacked got an id, a
    legacy map won, a string token already added stayed in the text."""
    made = {}
    for name in CASES:
        d = tmp_path / name
        d.mkdir()
        CASES[name](str(d))
        made[name] = T.TokenizersFile.load(str(d))
    assert made["harness_word_level"].render_chat(CHATS[0]) is None
    assert (made["harness_word_level"].bos_id,
            made["harness_word_level"].eos_id) == (None, None)
    t = made["bpe_strings_template_in_config"]
    assert (t.bos_id, t.eos_id) == (0, 1)
    assert t.render_chat(CHATS[1]).startswith(
        "<|begin|>[SYS] Be brief.\n<|user|> What's 2 + 2?<|end|>\n")
    assert t.render_chat(CHATS[1]).endswith(
        '<|assistant|>{"n": 5, "lt": "<"}')
    assert "dropped" not in t.render_chat(CHATS[1])
    assert t.decode(t.encode("it 's here , no ?")) == "it 's here , no ?"
    assert t.decode(t.encode("<|begin|>hi<|end|>", add_bos=False)) == "hi"
    t = made["bpe_added_token_dicts_clean_up_jinja_file"]
    assert t.decode(t.encode("it 's here , no ?")) == "it's here, no?"
    assert t.tk.token_to_id("<|pad|>") == t.vocab_size - 1
    assert "pad=<|pad|>" in t.render_chat(CHATS[0])
    assert "not used" not in t.render_chat(CHATS[0])
    t = made["bpe_legacy_special_tokens_map"]
    ids = {s: t.tk.token_to_id(s) for s in SPECIALS}
    # the map's, not the config's
    assert (t.bos_id, t.eos_id) == (ids["<|begin|>"], ids["<|end|>"])
    assert t.decode([ids["<|user|>"], ids["<|assistant|>"],
                     ids["<|begin|>"]]) == "<|user|>"
    t = made["bpe_bare_config_named_templates"]
    assert isinstance(t.chat_template, dict) and (t.bos_id, t.eos_id) == (
        None, None)
    assert t.render_chat(CHATS[0]).startswith("<|user|> Hello there\n")
    t = made["bpe_string_special_already_added"]
    ids = {s: t.tk.token_to_id(s) for s in SPECIALS}
    assert t.decode([ids["<|end|>"], ids["<|begin|>"]]) == "<|end|><|begin|>"
    assert t.tk.token_to_id("<|image|>") == t.vocab_size - 1
    images = token_byte_images(t, t.vocab_size)
    assert images[ids["<|end|>"]] == b""
    assert images[ids["<|user|>"]] == b"<|user|>"


def test_several_templates_and_no_default_is_an_error(tmp_path):
    byte_level_bpe(str(tmp_path), {"chat_template": [
        {"name": "rag", "template": "x"}, {"name": "tool_use",
                                           "template": "y"}]})
    with pytest.raises(ValueError, match="default"):
        T.TokenizersFile.load(str(tmp_path)).render_chat(CHATS[0])
    with pytest.raises(ValueError, match="default"):
        T.TransformersAuto.load(str(tmp_path)).render_chat(CHATS[0])


def test_a_template_may_raise_and_mark_the_generation(tmp_path):
    byte_level_bpe(str(tmp_path), {"chat_template": (
        "{% for m in messages %}{% if m['role'] == 'alien' %}"
        "{{ raise_exception('no such role') }}{% endif %}"
        "{% generation %}{{ m['content'] }}{% endgeneration %}"
        "{% endfor %}{{ strftime_now('%Y') | length }}")})
    mine, ref = (T.TokenizersFile.load(str(tmp_path)),
                 T.TransformersAuto.load(str(tmp_path)))
    assert mine.render_chat(CHATS[0]) == ref.render_chat(CHATS[0]) \
        == "Hello there4"
    import jinja2

    for t in (mine, ref):
        with pytest.raises(jinja2.exceptions.TemplateError, match="no such"):
            t.render_chat([{"role": "alien", "content": "x"}])


# -- which loader, and what it imports -----------------------------------------

def test_a_directory_with_tokenizer_json_never_imports_transformers(tmp_path):
    word_level(str(tmp_path))
    code = (
        "import sys\n"
        "from production_stack_tpu.engine.tokenizer import get_tokenizer\n"
        "from production_stack_tpu.engine.grammar import token_byte_images\n"
        f"tk = get_tokenizer({str(tmp_path)!r})\n"
        "tk.stream_decoder()(tk.encode('t3 t4'))\n"
        "assert tk.render_chat([{'role': 'user', 'content': 'x'}]) is None\n"
        "token_byte_images(tk, 64)\n"
        "print(tk.loader, 'transformers' in sys.modules)\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["tokenizers", "False"]


class Stub(T.HFTokenizer):
    loader = "transformers"
    bos_id = eos_id = None


@pytest.fixture
def auto_calls(monkeypatch):
    """`TransformersAuto.load` stubbed: the paths it was asked for."""
    calls = []
    monkeypatch.setattr(
        T.TransformersAuto, "load",
        classmethod(lambda cls, path: calls.append(path) or Stub()))
    return calls


@pytest.fixture
def warnings():
    """The warnings the module logs, taken at its own logger (a test that
    ran before may have cut the package's loggers off from the root's
    handlers, where `caplog` listens)."""
    records = []
    handler = logging.Handler(logging.WARNING)
    handler.emit = records.append
    T.logger.addHandler(handler)
    yield records
    T.logger.removeHandler(handler)


def test_a_directory_without_tokenizer_json_goes_to_auto_tokenizer(
        tmp_path, auto_calls, warnings):
    _dump(str(tmp_path), "tokenizer.model", "a SentencePiece file")
    tk = T.get_tokenizer(str(tmp_path))
    assert isinstance(tk, Stub) and auto_calls == [str(tmp_path)]
    assert not warnings


def test_an_unreadable_tokenizer_json_falls_through_with_one_warning(
        tmp_path, auto_calls, warnings):
    _dump(str(tmp_path), "tokenizer.json", "{ not a tokenizer")
    tk = T.get_tokenizer(str(tmp_path))
    assert isinstance(tk, Stub) and auto_calls == [str(tmp_path)]
    (warning,) = warnings
    assert "tokenizers library" in warning.getMessage()


def test_a_directory_nothing_reads_serves_bytes(
        tmp_path, monkeypatch, warnings):
    def refuse(cls, path):
        raise OSError("no tokenizer here")

    monkeypatch.setattr(T.TransformersAuto, "load", classmethod(refuse))
    tk = T.get_tokenizer(str(tmp_path))
    assert isinstance(tk, T.ByteTokenizer) and tk.loader == "bytes"
    assert tk.render_chat(CHATS[0]) is None
    (warning,) = warnings
    assert "byte tokenizer" in warning.getMessage()


def test_debug_perf_start_names_the_loader_and_chat_takes_the_template(
        tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        ModelConfig,
        SchedulerConfig,
    )
    from production_stack_tpu.engine.server import EngineServer
    from production_stack_tpu.parallel.mesh import MeshConfig

    byte_level_bpe(str(tmp_path), {
        "bos_token": "<|begin|>", "eos_token": "<|end|>",
        "chat_template": TEMPLATE})
    model = dataclasses.replace(
        ModelConfig.from_pretrained("tiny-llama"), tokenizer=str(tmp_path))
    server = EngineServer(EngineConfig(
        model=model, cache=CacheConfig(block_size=4, num_blocks=64),
        scheduler=SchedulerConfig(max_num_seqs=2, max_num_batched_tokens=64),
        mesh=MeshConfig(data=1, tensor=1)))
    assert server._render_chat(CHATS[0]).startswith(
        "<|begin|><|user|> Hello there<|end|>\n<|assistant|>")

    async def run():
        async with TestClient(TestServer(server.build_app())) as client:
            text = await (await client.get("/metrics")).text()
            return text, await (await client.get("/debug/perf")).json()

    text, perf = asyncio.run(run())
    assert perf["start"]["tokenizer_loader"] == "tokenizers"
    (line,) = [l for l in text.splitlines()
               if l.startswith("vllm:engine_start_tokenizer_seconds")]
    assert float(line.rpartition(" ")[2]) == pytest.approx(
        perf["start"]["seconds"]["tokenizer"])
