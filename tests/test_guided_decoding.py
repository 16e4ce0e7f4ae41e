"""Constrained decoding (guided_regex / guided_json): the regex→DFA→token
FSM compiler, and the engine e2e invariant that generated text ALWAYS
matches the grammar — even with random weights, sampling, multi-step fused
decode and mixed batches. (vLLM gets this from outlines/xgrammar with a
host-stepped FSM; here the FSM advances inside the fused decode loop —
engine/grammar.py, model_runner._grammar_mask.)"""

import dataclasses
import itertools
import json
import re as pyre

import numpy as np
import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.grammar import (
    RegexError,
    build_token_fsm,
    compile_regex,
    schema_to_regex,
    token_byte_images,
)
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.weights import init_or_load
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh


# -- compiler unit tests -----------------------------------------------------


CASES = [
    (r"\d{3}-\d{4}", ["555-1234"], ["55-1234", "5551234", "555-12345"]),
    (r"(foo|bar)+", ["foo", "bar", "foobarfoo"], ["", "fo", "fooba"]),
    (r"[a-c]*z", ["z", "abcz"], ["abz1", "dz"]),
    (r"a{2,4}", ["aa", "aaa", "aaaa"], ["a", "aaaaa"]),
    (r"a{2,}", ["aa", "aaaaaa"], ["a", ""]),
    (r"a{0,2}b", ["b", "ab", "aab"], ["aaab"]),
    (r"yes|no", ["yes", "no"], ["y", "yesno"]),
    (r"[^0-9]+", ["abc", "x!"], ["a1", ""]),
    (r"-?(0|[1-9]\d*)(\.\d+)?", ["0", "-12", "3.14"], ["00", ".5", "1."]),
    (r"\.x\\", [".x\\"], ["ax\\", ".x"]),
]


@pytest.mark.parametrize("pat,yes,no", CASES)
def test_regex_dfa(pat, yes, no):
    dfa = compile_regex(pat)
    for s in yes:
        st = dfa.walk(0, s.encode())
        assert st >= 0 and dfa.accept[st], (pat, s)
    for s in no:
        st = dfa.walk(0, s.encode())
        assert st < 0 or not dfa.accept[st], (pat, s)


def test_regex_fuzz_vs_python_re():
    pats = [r"(ab|a)*b", r"a(b|c){1,3}d?", r"[ab]{2}c*", r"(a|b)+c",
            r"a{2,}b?"]
    for pat in pats:
        dfa = compile_regex(pat)
        for L in range(0, 7):
            for tup in itertools.product("abcd", repeat=L):
                s = "".join(tup)
                want = pyre.fullmatch(pat, s) is not None
                st = dfa.walk(0, s.encode())
                got = st >= 0 and bool(dfa.accept[st])
                assert got == want, (pat, s, got, want)


def test_token_fsm_matches_byte_walk():
    """The vectorised token-table build must equal the per-token walk."""
    dfa = compile_regex(r"(ab|cd)*e?f")
    toks = [b"", b"a", b"b", b"ab", b"cd", b"abe", b"f", b"ef", b"abcdf"]
    fsm = build_token_fsm(dfa, toks)
    for v, bs in enumerate(toks):
        for s in range(dfa.n_states):
            want = dfa.walk(s, bs) if bs else -1
            assert fsm.trans[s, v] == want, (v, s)


def test_schema_to_regex():
    sc = schema_to_regex({
        "type": "object",
        "properties": {
            "name": {"type": "string"},
            "age": {"type": "integer"},
            "tags": {"type": "array", "items": {"type": "string"},
                     "maxItems": 3},
        },
    })
    dfa = compile_regex(sc)
    ok = '{"name": "bob", "age": 42, "tags": ["x", "y"]}'
    st = dfa.walk(0, ok.encode())
    assert st >= 0 and dfa.accept[st]
    bad = '{"name": 3, "age": 42, "tags": []}'
    st = dfa.walk(0, bad.encode())
    assert st < 0 or not dfa.accept[st]
    with pytest.raises(RegexError):
        schema_to_regex({"type": "object", "properties": {}})


def test_state_budget_enforced():
    with pytest.raises(RegexError, match="DFA states"):
        compile_regex("a{200}b{200}", max_states=64)


# -- engine e2e --------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=256),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=64,
            multi_step=2,
        ),
        mesh=MeshConfig(data=1, tensor=1),
        max_grammars=2, max_grammar_states=128,
    )
    mesh = build_mesh(cfg.mesh)
    params = init_or_load(cfg.model, mesh, seed=0)
    return cfg, mesh, params


def make_engine(setup, **over):
    cfg, mesh, params = setup
    cfg = dataclasses.replace(cfg, **over) if over else cfg
    return LLMEngine(cfg, mesh=mesh, params=params,
                     num_blocks=cfg.cache.num_blocks)


def _decode(eng, toks):
    return eng.tokenizer.decode(toks)


@pytest.mark.parametrize("temp", [0.0, 1.0])
def test_engine_output_matches_regex(setup, temp):
    """Random weights, greedy and sampled: output must fullmatch."""
    eng = make_engine(setup)
    pat = r"(yes|no)( indeed)?"
    sp = SamplingParams(temperature=temp, seed=11, max_tokens=16,
                        guided_regex=pat)
    out = eng.generate([[5, 6, 7]], sp)["offline-0"]
    text = _decode(eng, out)
    assert pyre.fullmatch(pat, text), repr(text)


def test_engine_guided_json(setup):
    """The flagship: random weights forced to emit schema-valid JSON."""
    eng = make_engine(setup)
    schema = {
        "type": "object",
        "properties": {
            "sentiment": {"enum": ["pos", "neg"]},
            "score": {"type": "integer"},
        },
    }
    sp = SamplingParams(temperature=0.9, seed=3, max_tokens=48,
                        guided_json=schema)
    out = eng.generate([[9, 8, 7, 6]], sp)["offline-0"]
    obj = json.loads(_decode(eng, out))
    assert obj["sentiment"] in ("pos", "neg")
    assert isinstance(obj["score"], int)


def test_guided_spans_multiple_dispatches(setup):
    """FSM state must survive across fused multi-step dispatch boundaries
    (multi_step=2, pattern needs ~8 tokens on the byte tokenizer)."""
    eng = make_engine(setup)
    pat = r"abcdefgh(ij)?"
    sp = SamplingParams(temperature=0.0, max_tokens=16, guided_regex=pat)
    out = eng.generate([[3, 4]], sp)["offline-0"]
    assert pyre.fullmatch(pat, _decode(eng, out))


def test_mixed_batch_unguided_rows_unchanged(setup):
    """An unconstrained request must produce identical greedy output
    whether or not a guided request shares its batch."""
    eng0 = make_engine(setup)
    free_sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    solo = eng0.generate([[1, 2, 3, 4]], free_sp)["offline-0"]
    eng = make_engine(setup)
    eng.add_request("free", prompt_token_ids=[1, 2, 3, 4], sampling=free_sp)
    eng.add_request("guided", prompt_token_ids=[5, 5],
                    sampling=SamplingParams(temperature=0.0, max_tokens=12,
                                            guided_regex=r"[xyz]{3}"))
    outs: dict = {}
    while eng.has_unfinished():
        for o in eng.step():
            outs.setdefault(o.request_id, []).extend(o.new_token_ids)
    assert outs["free"] == solo
    assert pyre.fullmatch(r"[xyz]{3}", _decode(eng, outs["guided"]))


def test_grammar_cache_and_slot_exhaustion(setup):
    eng = make_engine(setup)  # max_grammars=2
    sp = SamplingParams(temperature=0.0, max_tokens=6, guided_regex="[ab]+")
    eng.generate([[1, 2]], sp)
    # same pattern reuses the cached slot
    eng.generate([[3, 4]], sp)
    assert len(eng._grammar_cache) == 1
    # two more DISTINCT grammars: the second evicts the cold slot
    eng.generate([[1]], dataclasses.replace(sp, guided_regex="[cd]+"))
    eng.generate([[2]], dataclasses.replace(sp, guided_regex="[ef]+"))
    # three concurrent DISTINCT grammars exceed the bank
    eng.add_request("g1", prompt_token_ids=[1],
                    sampling=dataclasses.replace(sp, guided_regex="[gh]+"))
    eng.add_request("g2", prompt_token_ids=[2],
                    sampling=dataclasses.replace(sp, guided_regex="[ij]+"))
    with pytest.raises(ValueError, match="guided grammars"):
        eng.add_request("g3", prompt_token_ids=[3],
                        sampling=dataclasses.replace(sp,
                                                     guided_regex="[kl]+"))
    while eng.has_unfinished():
        eng.step()


def test_server_guided_endpoints(setup):
    """guided_regex / guided_json over the OpenAI surface + validation."""
    import asyncio

    from production_stack_tpu.engine.server import EngineServer

    cfg, mesh, params = setup
    eng = LLMEngine(cfg, mesh=mesh, params=params,
                    num_blocks=cfg.cache.num_blocks)
    server = EngineServer(cfg, engine=eng)

    async def fn():
        from aiohttp.test_utils import TestClient, TestServer

        async with TestClient(TestServer(server.build_app())) as client:
            r = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": "q: proceed? a:",
                "max_tokens": 12, "temperature": 0,
                "guided_regex": "(yes|no)",
            })
            assert r.status == 200
            text = (await r.json())["choices"][0]["text"]
            assert text in ("yes", "no"), repr(text)
            r = await client.post("/v1/chat/completions", json={
                "model": "tiny-llama",
                "messages": [{"role": "user", "content": "classify"}],
                "max_tokens": 40, "temperature": 0.8, "seed": 5,
                "guided_json": {"type": "object", "properties": {
                    "label": {"enum": ["a", "b"]}}},
            })
            assert r.status == 200
            content = (await r.json())["choices"][0]["message"]["content"]
            assert json.loads(content)["label"] in ("a", "b")
            # validation
            r = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": "x",
                "guided_regex": "(unclosed",
            })
            assert r.status == 400
            r = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": "x",
                "guided_regex": "a", "guided_choice": ["b"],
            })
            assert r.status == 400
            return True

    assert asyncio.run(fn())


def test_server_429_when_grammar_bank_exhausted(setup):
    """Bank exhaustion must be refused at validation (429), not surface as
    a failure after the handler committed (r2 advisor)."""
    import asyncio

    from production_stack_tpu.engine.server import EngineServer

    cfg, mesh, params = setup  # max_grammars=2
    eng = LLMEngine(cfg, mesh=mesh, params=params,
                    num_blocks=cfg.cache.num_blocks)
    server = EngineServer(cfg, engine=eng)
    # occupy both slots with live guided requests that CANNOT finish
    # before the asserts run (ignore_eos + large max_tokens): with short
    # holds the worker thread could complete them before the first POST,
    # freeing the slots and turning the expected 429 into a flaky 200
    # (r3 advisor). They are explicitly aborted below.
    sp = SamplingParams(temperature=0.0, max_tokens=512, ignore_eos=True)
    eng.add_request("hold-1", prompt_token_ids=[1], sampling=dataclasses
                    .replace(sp, guided_regex="[ab]+"))
    eng.add_request("hold-2", prompt_token_ids=[2], sampling=dataclasses
                    .replace(sp, guided_regex="[cd]+"))
    assert eng.grammar_slot_available(guided_regex="[ab]+")  # cached key
    assert not eng.grammar_slot_available(guided_regex="[xy]+")

    async def fn():
        from aiohttp.test_utils import TestClient, TestServer

        async with TestClient(TestServer(server.build_app())) as client:
            r = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": "x", "max_tokens": 4,
                "guided_regex": "[xy]+",
            })
            assert r.status == 429, await r.text()
            body = await r.json()
            assert body["error"]["type"] == "rate_limit_error"
            # a CACHED grammar is still admissible while slots are full
            r = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": "go", "max_tokens": 4,
                "temperature": 0, "guided_regex": "[ab]+",
            })
            assert r.status == 200, await r.text()
        return True

    assert asyncio.run(fn())
    eng.abort_request("hold-1")
    eng.abort_request("hold-2")
    while eng.has_unfinished():
        eng.step()


def test_guided_finishes_at_accept_state(setup):
    """A fully-matched pattern with no continuation must force EOS — the
    request finishes by stop, not by max_tokens."""
    eng = make_engine(setup)
    sp = SamplingParams(temperature=0.0, max_tokens=32,
                        guided_regex=r"ok")
    eng.add_request("fin", prompt_token_ids=[7, 7], sampling=sp)
    reasons = []
    toks: list = []
    while eng.has_unfinished():
        for o in eng.step():
            toks.extend(o.new_token_ids)
            if o.finished:
                reasons.append(o.finish_reason)
    assert reasons == ["stop"]
    # 'o', 'k', then EOS
    assert _decode(eng, toks) == "ok"
