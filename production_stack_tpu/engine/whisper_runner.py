"""Whisper execution: jitted prefill + chunked while-loop decode.

Drives models/whisper.py for the ``/v1/audio/transcriptions`` serving
path (reference deploys vLLM Whisper pods for this —
tutorials/23-whisper-api-transcription.md there; here the engine serves
the modality natively).

Execution shape (TPU-first):

- ``prefill``: ONE jit — encoder over the fixed 30 s mel window, cross
  K/V precompute, decoder prefill over the (bucketed, right-padded)
  forced-token sequence. Static shapes per prompt bucket.
- ``decode chunk``: ONE jit running up to CHUNK tokens in a
  ``lax.while_loop`` — no host round-trip per token (up to 448 steps
  per clip). The host loop around it streams each chunk's text
  incrementally and stops early on <|endoftext|>.
- Token suppression rides inside the chunk: special tokens above
  ``eot_id`` are masked at every step — in timestamp mode the
  ``<|t.tt|>`` tokens (above ``notimestamps_id``) are re-admitted as
  the segment boundaries srt/vtt/verbose_json are built from — and
  ``eot`` itself is additionally masked until at least one TEXT token
  has been emitted.
"""

from __future__ import annotations

import functools
import threading
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine import audio as audio_fe
from production_stack_tpu.engine.tokenizer import get_tokenizer
from production_stack_tpu.engine.weights import init_or_load
from production_stack_tpu.models import whisper as W
from production_stack_tpu.models.whisper import LANGUAGES
from production_stack_tpu.parallel.mesh import build_mesh

# decode chunk length: 32 tokens per dispatch keeps streaming latency
# ~chunk/decode-rate while amortising the dispatch RTT 32x
DECODE_CHUNK = 32
PROMPT_BUCKETS = (8, 32, 128)


def timestamp_suppress_mask(cfg, ids, timestamps, last_ts, ts_run):
    """The timestamp-rule part of the suppression mask (pure; unit-
    tested directly). Upstream ApplyTimestampRules distilled:

    - timestamps are non-decreasing (ids below ``last_ts`` masked);
    - an EQUAL timestamp is allowed only as the immediate second half
      of a boundary pair (``ts_run == 1``); after text, the next
      timestamp must be strictly greater (no zero-length segments);
    - after two consecutive timestamps (``ts_run >= 2``) the whole
      timestamp range is masked — text or eot must follow, so a
      degenerate decode can never loop on one timestamp forever.
    """
    import jax.numpy as jnp

    is_ts = ids > cfg.notimestamps_id
    below = jnp.where(ts_run == 1, ids < last_ts, ids <= last_ts)
    return timestamps & is_ts & (below | (ts_run >= 2))


class WhisperRunner:
    """Single-model transcription runner.

    Concurrency model: B=1 per device call (the 30 s window batch=1
    already saturates the MXU); an ADMISSION semaphore sized by
    ``scheduler.max_num_seqs`` bounds how many requests may hold live
    decode state (each admitted request owns cross-KV + self-KV device
    buffers), and within the admitted set the device lock is taken per
    32-token decode chunk so concurrent requests interleave instead of
    head-of-line blocking for whole clips."""

    def __init__(self, config: EngineConfig, mesh=None):
        cfg = config.model
        if cfg.architecture != "whisper":
            raise ValueError(f"not a whisper model: {cfg.architecture}")
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else build_mesh(config.mesh)
        self.params = init_or_load(cfg, self.mesh)
        self.tokenizer = get_tokenizer(cfg.tokenizer)
        self.lock = threading.Lock()
        # bound on LIVE decode states (per-request KV buffers on device):
        # without it a burst of uploads would each allocate cross-KV +
        # self-KV before queueing on the chunk lock and OOM HBM
        self.admit = threading.BoundedSemaphore(
            max(config.scheduler.max_num_seqs, 1))
        self.chunk_frames = cfg.n_audio_ctx * 2
        # langs actually present in this vocab
        self.languages = LANGUAGES[: cfg.n_langs]

    # -- jitted programs ----------------------------------------------------

    @functools.cached_property
    def _encode(self):
        cfg = self.cfg

        @jax.jit
        def enc_fn(params, mel):
            enc = W.encode(cfg, params, mel)
            return W.cross_kv(cfg, params, enc)

        return enc_fn

    @functools.cached_property
    def _dec_prefill(self):
        """Decoder prefill over the (bucketed) forced tokens. Split from
        the encoder jit so auto language detection and the real prefill
        SHARE one encoder pass (the encoder is ~half of Whisper's FLOPs
        at short outputs — r5 review)."""
        cfg = self.cfg

        @functools.partial(jax.jit, static_argnums=0)
        def prefill(P: int, params, ck, cv, tokens, valid):
            kv = W.init_self_kv(cfg, 1, cfg.max_model_len)
            logits, kv = W.decode_tokens(
                cfg, params, tokens, jnp.zeros((1,), jnp.int32), kv, ck, cv,
                valid)
            # logits at the LAST REAL position seed generation
            last = jnp.take_along_axis(
                logits, (valid - 1)[:, None, None], axis=1)[:, 0]
            return kv, last

        return prefill

    @functools.cached_property
    def _chunk(self):
        cfg = self.cfg
        V = cfg.vocab_size
        ids = jnp.arange(V, dtype=jnp.int32)
        # vocab layout: eot < sot < langs < tasks < ... < notimestamps <
        # timestamps. Default mode suppresses everything above eot;
        # timestamp mode re-admits the timestamp tokens (the segment
        # boundaries srt/vtt/verbose_json are built from).
        special = ids > cfg.eot_id
        non_ts_special = (ids > cfg.eot_id) & (ids <= cfg.notimestamps_id)

        def suppress(logits, n_gen, timestamps, last_ts, ts_run):
            mask = jnp.where(timestamps, non_ts_special, special)
            mask = mask | timestamp_suppress_mask(
                cfg, ids, timestamps, last_ts, ts_run)
            logits = jnp.where(mask, -jnp.inf, logits)
            return jnp.where((ids == cfg.eot_id) & (n_gen < 1),
                             -jnp.inf, logits)

        def sample(logits, n_gen, temp, key, timestamps, last_ts, ts_run):
            """-> (token, its log-probability under the suppressed
            distribution — verbose_json's avg_logprob input)."""
            logits = suppress(logits, n_gen, timestamps, last_ts, ts_run)
            greedy = jnp.argmax(logits).astype(jnp.int32)
            drawn = jax.random.categorical(
                key, logits / jnp.maximum(temp, 1e-6)).astype(jnp.int32)
            tok = jnp.where(temp > 0.0, drawn, greedy)
            logp = jax.nn.log_softmax(logits)[tok]
            return tok, logp

        @jax.jit
        def chunk(params, kv, ck, cv, cur_len, n_gen, last_logits,
                  limit, temp, key, timestamps, last_ts, ts_run):
            """Generate up to DECODE_CHUNK tokens from ``last_logits``.

            ``last_ts`` carries the highest timestamp id emitted so far
            (0 = none) and ``ts_run`` the current consecutive-timestamp
            run length across chunks, so the timestamp rules hold
            globally. Returns (buf (CHUNK,), logp_buf (CHUNK,),
            n_emitted, kv, cur_len, n_gen, last_logits, done, last_ts,
            ts_run)."""
            buf0 = jnp.zeros((DECODE_CHUNK,), jnp.int32)
            logp0 = jnp.zeros((DECODE_CHUNK,), jnp.float32)

            def cond(c):
                i, _, _, _, cur, n, _, done, _, _, _ = c
                return (~done) & (i < DECODE_CHUNK) & (cur < limit)

            def body(c):
                (i, buf, logp_buf, kv, cur, n, logits, done, key, lts,
                 run) = c
                key, sub = jax.random.split(key)
                tok, logp = sample(logits[0], n, temp, sub, timestamps,
                                   lts, run)
                buf = buf.at[i].set(tok)
                logp_buf = logp_buf.at[i].set(logp)
                is_eot = tok == cfg.eot_id
                new_logits, kv = W.decode_tokens(
                    cfg, params, tok[None, None], cur[None], kv, ck, cv,
                    jnp.ones((1,), jnp.int32))
                # n counts TEXT tokens (eot-release guard): a leading
                # <|0.00|> must not satisfy "at least one text token"
                n_next = n + jnp.where(tok < cfg.eot_id, 1, 0)
                is_ts = tok > cfg.notimestamps_id
                lts = jnp.where(is_ts, jnp.maximum(lts, tok), lts)
                run = jnp.where(is_ts, run + 1, jnp.int32(0))
                return (i + 1, buf, logp_buf, kv, cur + 1, n_next,
                        new_logits[:, 0], is_eot, key, lts, run)

            (i, buf, logp_buf, kv, cur, n, logits, done, _, last_ts,
             ts_run) = lax.while_loop(
                cond, body,
                (jnp.int32(0), buf0, logp0, kv, cur_len, n_gen,
                 last_logits, jnp.bool_(False), key, last_ts, ts_run))
            return (buf, logp_buf, i, kv, cur, n, logits, done, last_ts,
                    ts_run)

        return chunk

    # -- host-side API ------------------------------------------------------

    def _usable_buckets(self) -> list[int]:
        # a bucket must leave at least one decode slot in the context
        return [b for b in PROMPT_BUCKETS if b < self.cfg.max_model_len]

    def _bucket(self, n: int) -> int:
        for b in self._usable_buckets():
            if n <= b:
                return b
        raise audio_fe.AudioError(
            f"prompt of {n} tokens exceeds the decoder context "
            f"({self.cfg.max_model_len})"
        )

    def _forced_tokens(self, language: Optional[str], task: str,
                       prompt: Optional[str],
                       timestamps: bool = False) -> list[int]:
        cfg = self.cfg
        forced: list[int] = []
        if prompt:
            ids = self.tokenizer.encode(prompt, add_bos=False)
            # truncate from the LEFT (keep recent context, as upstream)
            # to the largest prompt bucket this model can serve
            keep = max(self._usable_buckets()[-1] - 5, 1)
            forced += [cfg.sot_prev_id] + ids[-keep:]
        forced.append(cfg.sot_id)
        if language is not None:
            try:
                lang_idx = self.languages.index(language)
            except ValueError:
                raise audio_fe.AudioError(
                    f"unsupported language {language!r}; supported: "
                    f"{', '.join(self.languages)}"
                ) from None
            forced.append(cfg.lang_base_id + lang_idx)
        forced.append(cfg.translate_id if task == "translate"
                      else cfg.transcribe_id)
        if not timestamps:  # timestamp mode lets the model emit <|t.tt|>
            forced.append(cfg.notimestamps_id)
        return forced

    def strip_timestamps(self, tokens: list[int]) -> list[int]:
        """Drop <|t.tt|> tokens before plain-text decoding (v2 HF
        tokenizers don't even carry them in vocab)."""
        return [t for t in tokens if t <= self.cfg.notimestamps_id]

    def segments_from_tokens(self, tokens: list[int], duration: float,
                             logprobs: Optional[list[float]] = None,
                             ) -> list[dict]:
        """Split a timestamp-mode token stream into segments.

        Timestamp tokens encode ``(id - notimestamps_id - 1) * 0.02``
        seconds; text between a start and end timestamp is one segment.
        Lenient parse (the decoder is not grammar-constrained): an
        unclosed final segment ends at the clip duration. ``logprobs``
        (aligned with ``tokens``) adds per-segment ``avg_logprob``;
        ``compression_ratio`` (OpenAI schema: gzip-incompressibility of
        the text, the repetition-loop detector) is always computed."""
        import zlib

        cfg = self.cfg
        base = cfg.notimestamps_id + 1
        lps = logprobs if logprobs and len(logprobs) == len(tokens) \
            else [0.0] * len(tokens)

        def ts(tok):
            return (tok - base) * 0.02

        def emit(start, end, text_toks, text_lps):
            text = self.tokenizer.decode(text_toks)
            raw = text.encode() or b" "
            return {
                "start": round(start, 2), "end": round(end, 2),
                "tokens": text_toks, "text": text,
                "avg_logprob": round(
                    sum(text_lps) / max(len(text_lps), 1), 4),
                "compression_ratio": round(
                    len(raw) / max(len(zlib.compress(raw)), 1), 3),
            }

        segments: list[dict] = []
        start = 0.0
        text_toks: list[int] = []
        text_lps: list[float] = []
        for t, lp in zip(tokens, lps):
            if t > cfg.notimestamps_id:  # timestamp token
                if text_toks:
                    # ungrammatical decodes can emit a smaller timestamp
                    # after a larger one: clamp so no cue ever has
                    # start > end (subtitle players reject those)
                    segments.append(
                        emit(start, max(ts(t), start), text_toks,
                             text_lps))
                    text_toks, text_lps = [], []
                start = ts(t)
            elif t != cfg.eot_id:
                text_toks.append(t)
                text_lps.append(lp)
        if text_toks:
            segments.append(
                emit(start, max(duration, start), text_toks, text_lps))
        return segments

    def _sot_logits(self, ck, cv) -> np.ndarray:
        """Next-token logits at the <|startoftranscript|> position
        (prefill of the bare SOT token). Caller holds the lock and
        supplies the shared cross K/V. Feeds both language detection and
        ``no_speech_prob`` — Whisper defines the no-speech probability
        HERE, not at the first post-prefix prediction where the forced
        task/language tokens have already conditioned the model toward
        emitting text."""
        cfg = self.cfg
        P = PROMPT_BUCKETS[0]
        tokens = np.zeros((1, P), np.int32)
        tokens[0, 0] = cfg.sot_id
        _, last = self._dec_prefill(
            P, self.params, ck, cv, jnp.asarray(tokens),
            jnp.ones((1,), jnp.int32))
        return np.asarray(last[0])

    def _detect_language_from(self, ck, cv) -> str:
        """argmax over the language tokens after <|startoftranscript|>.
        Caller holds the lock and supplies the shared cross K/V."""
        cfg = self.cfg
        logits = self._sot_logits(ck, cv)
        lang_logits = logits[cfg.lang_base_id:cfg.lang_base_id + cfg.n_langs]
        return self.languages[int(np.argmax(lang_logits))]

    def detect_language(self, features: np.ndarray) -> str:
        with self.lock:
            ck, cv = self._encode(self.params, jnp.asarray(features)[None])
            return self._detect_language_from(ck, cv)

    def validate_request(self, language: Optional[str], task: str,
                         prompt: Optional[str]) -> None:
        """Raise AudioError for bad language/oversized prompt BEFORE any
        device work (the server maps it to 400 — after the SSE stream
        has started a late error can only kill the connection)."""
        self._bucket(len(self._forced_tokens(
            language if language is not None else
            (self.languages[0] if self.languages else None),
            task, prompt)))

    def transcribe_stream(
        self,
        features: np.ndarray,           # (n_mels, chunk_frames)
        language: Optional[str] = None,
        task: str = "transcribe",
        prompt: Optional[str] = None,
        temperature: float = 0.0,
        max_tokens: Optional[int] = None,
        seed: int = 0,
        info: Optional[dict] = None,
        timestamps: bool = False,
    ) -> Iterator[list[int]]:
        """Yields lists of newly generated token ids (eot stripped; with
        ``timestamps`` the stream includes <|t.tt|> tokens — see
        ``segments_from_tokens``). ``info`` (if given) receives
        ``{"language": <used-or-detected>}`` before the first yield."""
        cfg = self.cfg
        # admission: bound the number of requests holding live device
        # buffers (released in the finally when the generator finishes
        # or is closed)
        self.admit.acquire()
        try:
            with self.lock:
                # ONE encoder pass shared by detection and transcription,
                # and ONE SOT prefill shared by language detection and
                # the no-speech probability
                ck, cv = self._encode(self.params,
                                      jnp.asarray(features)[None])
                sot_logits = None
                if (language is None and cfg.n_langs) or info is not None:
                    sot_logits = self._sot_logits(ck, cv)
                if language is None and cfg.n_langs:
                    lang_logits = sot_logits[
                        cfg.lang_base_id:cfg.lang_base_id + cfg.n_langs]
                    language = self.languages[int(np.argmax(lang_logits))]
            if info is not None:
                info["language"] = language
                # Whisper's VAD signal: P(<|nospeech|>) at the SOT
                # position (vocab layout: nospeech sits right below
                # notimestamps), from the same prefill language
                # detection uses
                z = sot_logits.astype(np.float64)
                e = np.exp(z - z.max())
                info["no_speech_prob"] = float(
                    e[cfg.notimestamps_id - 1] / e.sum())
            forced = self._forced_tokens(language, task, prompt,
                                         timestamps=timestamps)
            P = self._bucket(len(forced))
            tokens = np.zeros((1, P), np.int32)
            tokens[0, : len(forced)] = forced
            n_forced = len(forced)
            limit = cfg.max_model_len
            if max_tokens is not None:
                limit = min(limit, n_forced + max(int(max_tokens), 1))
            with self.lock:
                kv, last = self._dec_prefill(
                    P, self.params, ck, cv, jnp.asarray(tokens),
                    jnp.full((1,), n_forced, jnp.int32))
            cur = jnp.full((), n_forced, jnp.int32)
            n_gen = jnp.zeros((), jnp.int32)
            key = jax.random.PRNGKey(seed)
            done = False
            last_ts = jnp.int32(0)
            ts_run = jnp.int32(0)
            while not done:
                key, sub = jax.random.split(key)
                # lock per CHUNK, not per request: every request's decode
                # state (kv/ck/cv/cur) is its own arrays, so admitted
                # transcriptions interleave at chunk granularity instead
                # of head-of-line-blocking for whole clips
                with self.lock:
                    (buf, logps, n_emit, kv, cur, n_gen, last, done_dev,
                     last_ts, ts_run) = self._chunk(
                        self.params, kv, ck, cv, cur, n_gen, last,
                        jnp.int32(limit), jnp.float32(temperature),
                        sub, jnp.bool_(timestamps), last_ts, ts_run)
                n_emit = int(n_emit)
                out = np.asarray(buf[:n_emit]).tolist()
                out_lp = np.asarray(logps[:n_emit]).tolist()
                done = bool(done_dev) or n_emit < DECODE_CHUNK
                kept = [(t, lp) for t, lp in zip(out, out_lp)
                        if t != cfg.eot_id]
                if info is not None:  # aligned with every yielded token
                    info.setdefault("logprobs", []).extend(
                        lp for _, lp in kept)
                yield [t for t, _ in kept]
        finally:
            self.admit.release()

    def transcribe(self, features: np.ndarray, **kw) -> list[int]:
        out: list[int] = []
        for piece in self.transcribe_stream(features, **kw):
            out.extend(piece)
        return out
