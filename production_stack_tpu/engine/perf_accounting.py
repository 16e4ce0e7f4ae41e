"""Continuous goodput accounting: live MFU / HBM-bandwidth estimates and
jit compile-event tracking.

docs/roofline.md works out per-dispatch FLOP and byte costs by hand;
this module runs the same arithmetic on every dispatch, against the
published peaks of the device it runs on (``DEVICE_PEAKS``), so the
numbers are permanent gauges instead of one-off measurements. A device
with no entry and no ``--perf-peak-*`` override reports token rates,
bytes and compile events but NO utilization — a ratio against another
chip's peak is not a utilization:

* ``PerfAccountant`` — a sliding window of per-dispatch FLOP/byte/token
  estimates (prefill and decode recorded separately by the engine's
  ``_run_*`` paths), reduced to ``vllm:model_flops_utilization``,
  ``vllm:hbm_bandwidth_utilization`` and
  ``vllm:tokens_per_second{phase}`` at scrape time, plus periodic HBM
  occupancy snapshots from ``device.memory_stats()``.

  On a multi-chip mesh the same window also carries an ICI axis:
  per-dispatch collective bytes (all-reduce of the two row-parallel
  matmul outputs per layer, all-gather of the vocab-sharded logits at
  every consumed stream position) derived from the sharding degree +
  model geometry — no collective is instrumented, the bytes are
  arithmetic, exactly like the FLOP/HBM estimates. Reduced to
  ``vllm:ici_bandwidth_utilization`` and
  ``vllm:collective_bytes_total{op}``; the FLOP/HBM ceilings scale by
  the chip count so MFU is fleet-honest (a TP=4 engine reading the
  single-chip peak would report 4x the truth).
* ``CompileTracker`` — wraps each jitted program; a never-seen argument
  signature (shapes/dtypes + static kwargs) is exactly what makes XLA
  build a new executable, so the first call per signature is one *build*:
  a compile event, its wall time split into trace / lower / compile or
  cache load / first run by jax's own monitoring events (``BuildStages``).
  After ``mark_steady()`` (warmup complete) any new signature also ticks
  the unexpected-recompile counter the alert rules treat as a bug signal.

Token counts are LIVE tokens, not padded — padding waste is supposed to
show up as lost MFU; that is the goodput story.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from collections import deque
from typing import Callable, Dict, Optional, Tuple

from production_stack_tpu.engine.tracing import build_annotation
from production_stack_tpu.tenancy import OTHER, fold_records, split_shares

# Published per-chip peaks keyed by jax's ``device_kind``:
# (bf16 TFLOP/s, HBM GB/s, ICI GB/s). The collective cost model below
# counts per-chip bytes on the wire, so the ICI figure is per chip too.
# Source — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s (= 200 GB/s) chip-to-chip interconnect.
DEVICE_PEAKS: Dict[str, Tuple[float, float, float]] = {
    "TPU v5 lite": (197.0, 819.0, 200.0),
}

_log = logging.getLogger(__name__)
_EVENT_TAIL = 256  # builds kept verbatim for /debug/perf


def _stack_param_count(model_cfg) -> int:
    """Matrices of the decoder layers, Llama geometry, from config."""
    h = model_cfg.hidden_size
    inter = model_cfg.intermediate_size
    heads = model_cfg.num_heads
    if getattr(model_cfg, "is_latent", False):
        # latent attention: the two low-rank paths and the output projection
        c, r = model_cfg.kv_lora_rank, model_cfg.q_lora_rank
        # the query's low-rank pair, or (rank 0) its one direct projection
        qkv = ((h * r + r * heads * model_cfg.head_dim if r
                else h * heads * model_cfg.head_dim)
               + h * model_cfg.latent_width
               + c * heads * (model_cfg.qk_nope_head_dim
                              + model_cfg.v_head_dim)
               + heads * model_cfg.v_head_dim * h)
    else:
        qkv = (h * heads * model_cfg.head_dim
               + 2 * h * model_cfg.num_kv_heads * model_cfg.head_dim
               + heads * model_cfg.head_dim * h)
    # every expert: this is what is HELD; what a token multiplies with is
    # PerfAccountant.active_param_count
    mlp = 3 * h * inter * max(getattr(model_cfg, "num_experts", 0) or 1, 1)
    dense = getattr(model_cfg, "dense_layers", 0)
    return int((model_cfg.num_layers - dense) * (qkv + mlp)
               + dense * (qkv + 3 * h * getattr(
                   model_cfg, "dense_intermediate_size", 0)))


def estimate_param_count(model_cfg) -> int:
    """Llama-geometry parameter count from config — the fallback when the
    runner's param tree isn't addressable (staged pipeline runner)."""
    return int(2 * model_cfg.vocab_size * model_cfg.hidden_size
               + _stack_param_count(model_cfg))


def _ratio(rate: float, peak: float) -> Optional[float]:
    """rate / peak, or None where the device's peak is not known."""
    return rate / peak if peak > 0 else None


def _dtype_bytes(dtype: str) -> int:
    return 4 if "32" in str(dtype) else 2


# -- a program's first call, in stages ----------------------------------------
# jax times its own stages of building a program and says so through
# `jax.monitoring` (jax 0.9.0: `dispatch.log_elapsed_time` records a scalar
# at a stage's entry and a duration at its exit; `compiler.
# compile_or_get_cached`, which `backend_compile_duration` wraps, records
# `cache_hits` and the retrieval time when the persistent cache answered,
# and nothing of the kind when it compiled). A stage can open inside another
# (a jitted helper traced while the step program is: `trace` in `trace`), so
# a stage is charged its own time minus what opened inside it.
BUILD_STAGES = ("trace", "lower", "compile", "cache_load", "first_run")
_JAX_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile_or_load",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


def new_build(kind: str, bucket: str) -> dict:
    """One build as /debug/perf `builds` shows it. ``seconds`` is the first
    call's wall time and ``stages`` adds up to it; ``cache_hits`` /
    ``cache_misses`` count the backend's compile requests the persistent
    cache answered / did not (a build can make more than one: the program
    and a helper), ``cache_retrieval_seconds`` is the part of `cache_load`
    spent reading and deserializing (the rest: the key's hash); ``at`` is
    its opening on `StepClock.now()`'s clock, ``ts`` the same on the
    wall's."""
    return {"kind": kind, "bucket": bucket, "at": time.monotonic(),
            "seconds": 0.0,
            "stages": dict.fromkeys(BUILD_STAGES, 0.0),
            "cache_hits": 0, "cache_misses": 0, "cache_hit": False,
            "cache_retrieval_seconds": 0.0, "engine_step": None,
            "unexpected": False, "ts": time.time()}


class BuildStages:
    """jax's monitoring events, charged to the build open on the thread
    they fire on (`building`), else to the programs nobody tracks: kind
    `other` (`init_params`, `lay_out`'s transposes, the pool's zeros, the
    sampling helpers, every eager operation's small program), one entry a
    backend compile request, named by jax's own name for the function.
    One instance a process (`build_stages`): jax keeps listeners for good.
    The listeners run only while jax builds something; a call of a
    program already built fires none."""

    def __init__(self):
        self._local = threading.local()
        self._lock = threading.Lock()
        self.other: deque = deque(maxlen=_EVENT_TAIL)  # entries, `other`
        self.other_seconds = dict.fromkeys(BUILD_STAGES, 0.0)
        self.other_builds = self.other_hits = self.other_misses = 0
        self._other_names: dict = {}

    def register(self) -> "BuildStages":
        import jax.monitoring as monitoring

        monitoring.register_scalar_listener(self._on_enter)
        monitoring.register_event_duration_secs_listener(self._on_exit)
        monitoring.register_event_listener(self._on_event)
        return self

    def _state(self):
        tl = self._local
        if not hasattr(tl, "stack"):
            tl.stack, tl.build, tl.hit = [], None, False
            tl.pending = new_build("other", "")
        return tl

    @contextlib.contextmanager
    def building(self, build: dict):
        """``build`` takes this thread's events until the block ends."""
        tl = self._state()
        tl.build = build
        try:
            yield
        finally:
            tl.build = None

    def _on_enter(self, event: str, value, **kwargs) -> None:
        if event in _JAX_STAGES:
            self._state().stack.append(0.0)  # seconds opened inside it

    def _on_event(self, event: str, **kwargs) -> None:
        if event == _CACHE_HIT:
            self._state().hit = True

    def _on_exit(self, event: str, seconds: float, **kwargs) -> None:
        stage = _JAX_STAGES.get(event)
        if stage is None and event != _CACHE_RETRIEVAL:
            return
        tl = self._state()
        build = tl.build or tl.pending
        if stage is None:
            build["cache_retrieval_seconds"] += seconds
            return
        own = max(seconds - (tl.stack.pop() if tl.stack else 0.0), 0.0)
        if tl.stack:
            tl.stack[-1] += seconds
        if stage == "compile_or_load":
            stage = "cache_load" if tl.hit else "compile"
            build["cache_hits" if tl.hit else "cache_misses"] += 1
            tl.hit = False
        build["stages"][stage] += own
        if tl.build is None and stage in ("compile", "cache_load"):
            self._close_other(tl, str(kwargs.get("fun_name", "?")))

    def _close_other(self, tl, fun_name: str) -> None:
        """A program nobody tracks has reached the backend: what this
        thread traced and lowered since the last one is its entry."""
        entry, tl.pending = tl.pending, new_build("other", "")
        entry["seconds"] = sum(entry["stages"].values())
        entry["cache_hit"] = entry["cache_misses"] == 0
        # when it opened, about: its stages may lie apart
        entry["at"] = time.monotonic() - entry["seconds"]
        entry["ts"] = time.time() - entry["seconds"]
        with self._lock:
            n = self._other_names[fun_name] = (
                self._other_names.get(fun_name, 0) + 1)
            entry["bucket"] = fun_name if n == 1 else f"{fun_name}#{n}"
            self.other.append(entry)
            self.other_builds += 1
            self.other_hits += entry["cache_hits"]
            self.other_misses += entry["cache_misses"]
            for stage, sec in entry["stages"].items():
                self.other_seconds[stage] += sec


_build_stages: Optional[BuildStages] = None


def build_stages() -> BuildStages:
    """The process's one `BuildStages`, registered with jax at first use."""
    global _build_stages
    if _build_stages is None:
        _build_stages = BuildStages().register()
    return _build_stages


# the step programs' static flags that select a variant beside `greedy_only`
_VARIANT_FLAGS = ("want_logprobs", "use_grammar", "use_penalties",
                  "use_controls")


def program_bucket(args: tuple, kwargs: dict) -> str:
    """What tells a program's signature from the others of its kind, from
    what the caller already knows: ``w<stream width>`` (the packed step
    input's `tokens` field by the ``layout`` it is passed with: a ragged
    program's stream, a decode program's slots; else the shape of the first
    argument that has one) and the static flags that select a variant, e.g.
    ``w512:greedy``, ``w64:sampled+logprobs``."""
    layout = kwargs.get("layout")
    if layout is not None:
        width = "w" + str(max(layout.fields[0][1]))
    else:
        shape = next((a.shape for a in args if getattr(a, "shape", None)), ())
        width = "x".join(str(int(d)) for d in shape) or "-"
    flags = []
    if "greedy_only" in kwargs:
        flags.append("greedy" if kwargs["greedy_only"] else "sampled")
    flags += [k.split("_", 1)[1] for k in _VARIANT_FLAGS if kwargs.get(k)]
    if kwargs.get("lora_bank") is not None:
        flags.append("lora")
    # a static flag of a program this module does not know
    flags += sorted(k for k, v in kwargs.items() if v is True
                    and k not in (*_VARIANT_FLAGS, "greedy_only"))
    return width + (":" + "+".join(flags) if flags else "")


class CompileTracker:
    """Wrap a jitted callable and surface its builds.

    The signature key mirrors jax's compilation-cache key closely enough
    for accounting: per-argument (shape, dtype) for arrays, literal
    values for hashable statics, structural markers for pytrees. A new
    key means jax builds a new executable: that first call is timed and
    its wall time split by stage (``BuildStages``; `first_run` is what is
    left: the arguments' transfer, the dispatch, the execution where the
    caller waits for it); it runs inside a `build` profiler annotation
    (engine/tracing.py). Steady-state calls of a seen signature are
    dispatch-only and are not timed. A build is named ``kind:bucket``
    (``program_bucket``); a second signature of the same name gets
    ``#2``, so a name is one program."""

    def __init__(self, kind: str, fn: Callable, observer: Callable):
        self.kind = kind
        self.fn = fn
        self.observer = observer
        self._seen: set = set()
        self._names: dict = {}
        self._stages = build_stages()

    def _sig(self, v):
        shape = getattr(v, "shape", None)
        if shape is not None:
            return ("arr", tuple(shape), str(getattr(v, "dtype", "?")))
        if isinstance(v, (bool, int, float, str, type(None))):
            return v
        if isinstance(v, (tuple, list)):
            return ("seq", tuple(self._sig(x) for x in v))
        if isinstance(v, dict):
            return ("map", tuple(sorted(str(k) for k in v)))
        return type(v).__name__

    def __call__(self, *args, **kwargs):
        key = (tuple(self._sig(a) for a in args),
               tuple((k, self._sig(v)) for k, v in sorted(kwargs.items())))
        if key in self._seen:
            return self.fn(*args, **kwargs)
        bucket = program_bucket(args, kwargs)
        n = self._names.get(bucket, 0) + 1
        build = new_build(self.kind, bucket if n == 1 else f"{bucket}#{n}")
        t0 = build["at"]
        with build_annotation(self.kind, build["bucket"]) as ann, \
                self._stages.building(build):
            out = self.fn(*args, **kwargs)
            build["cache_hit"] = (build["cache_hits"] > 0
                                  and build["cache_misses"] == 0)
            ann.set_metadata(cache_hit=int(build["cache_hit"]))
        stages = build["stages"]
        build["seconds"] = time.monotonic() - t0
        stages["first_run"] = max(
            build["seconds"] - sum(stages.values()), 0.0)
        self._seen.add(key)
        self._names[bucket] = n
        self.observer(self.kind, build["bucket"], build["seconds"], build)
        return out


def wrap_runner_programs(runner, observer: Callable) -> None:
    """Install ``CompileTracker`` proxies over a runner's jitted programs
    (speculative verify has no program of its own — it is fused into
    ``_ragged``)."""
    for attr in ("_decode_multi", "_sample", "_ragged"):
        fn = getattr(runner, attr, None)
        if fn is None or isinstance(fn, CompileTracker):
            continue
        setattr(runner, attr, CompileTracker(attr.lstrip("_"), fn, observer))


class PerfAccountant:
    """Sliding-window goodput accounting + compile-event bookkeeping.

    Recording happens on the engine (device) thread; snapshots are read
    from the HTTP handlers — a lock keeps the two honest."""

    def __init__(self, model_cfg, *, param_count: int, param_bytes: int,
                 window: float = 60.0, peak_tflops: float = 0.0,
                 peak_hbm_gbps: float = 0.0, hbm_poll_interval: float = 5.0,
                 n_chips: int = 1, tensor_parallel: int = 1,
                 peak_ici_gbps: float = 0.0, tenant_metering: bool = True,
                 tenant_top_k: int = 8):
        self.window = max(window, 1.0)
        self.n_chips = max(int(n_chips), 1)
        self.tp = max(int(tensor_parallel), 1)
        # FLOP and weight-stream costs below are GLOBAL (whole model), so
        # the matching ceilings are the mesh's aggregate peaks. A peak of
        # 0 means "not known for this device": that axis reports no
        # utilization and drops out of the cost model.
        self.peak_flops = peak_tflops * 1e12 * self.n_chips
        self.peak_hbm = peak_hbm_gbps * 1e9 * self.n_chips
        # collective bytes are counted PER CHIP on the wire (every ring
        # participant moves the same bytes), so the ICI ceiling stays the
        # per-chip link bandwidth
        self.peak_ici = peak_ici_gbps * 1e9
        self.param_count = max(int(param_count), 1)
        self.param_bytes = max(int(param_bytes), 1)
        self.hbm_poll_interval = hbm_poll_interval
        cfg = model_cfg
        # MoE: a token multiplies with its k experts of X only, and a
        # dispatch reads only the experts some token was routed to. FLOPs
        # are reckoned on the ACTIVE parameters, bytes on the experts
        # touched (expected under uniform routing: 1 - (1 - k/X)^tokens)
        X = getattr(cfg, "num_experts", 0) or 0
        self._expert_share = X and (
            cfg.num_layers * X * 3 * cfg.hidden_size * cfg.intermediate_size
            / self.param_count)
        self._route_share = X and cfg.num_experts_per_tok / X
        self.active_param_count = self.param_count * (
            1.0 - self._expert_share * (1.0 - self._route_share))
        # a looped stack (loop_passes = U > 1) multiplies every token with
        # its layers' matrices once a pass and reads them once a pass,
        # U - 1 times more than they are held; and it attends over, and
        # keeps, keys and values for every (pass, layer) pair
        self.loop_passes = int(getattr(cfg, "loop_passes", 1))
        # layers that keep keys and values per token (a hybrid stack's
        # recurrent layers keep state per slot instead), and what a token
        # of context holds in them: the cache layout's own numbers
        self.cache_layers = cfg.cache_layers
        layer_passes = cfg.num_layers * self.loop_passes
        repeats = ((self.loop_passes - 1) * _stack_param_count(cfg)
                   if self.loop_passes > 1 else 0)
        self.active_param_count += repeats
        self._loop_extra_bytes = (repeats * self.param_bytes
                                  / self.param_count)
        # operations a (query token, context token) pair costs: QK and PV
        # over head_dim a head, or, absorbed, over the latent row and the
        # latent
        self._attn_per_tok_ctx = 2 * self.cache_layers * cfg.num_heads * (
            cfg.latent_width + cfg.kv_lora_rank if cfg.is_latent
            else 2 * cfg.head_dim)
        self._kv_bytes_per_tok = cfg.kv_bytes_per_token
        # ICI cost model (docs/roofline.md "Multi-chip"), zero at tp=1:
        # each layer's two row-parallel matmuls (attention out-proj, MLP
        # down-proj) end in an all-reduce of the (tokens, hidden)
        # activation; a ring all-reduce moves 2(tp-1)/tp x payload per
        # chip. The vocab axis shards the logits, so every stream position
        # whose logits are consumed (sampled rows + speculative verify
        # columns) pays an all-gather of (tp-1)/tp x vocab f32 per chip.
        ar_fac = 2.0 * (self.tp - 1) / self.tp
        ag_fac = (self.tp - 1) / self.tp
        self._ar_bytes_per_tok = (2 * layer_passes * cfg.hidden_size
                                  * _dtype_bytes(cfg.dtype) * ar_fac)
        self._ag_bytes_per_row = cfg.vocab_size * 4 * ag_fac
        self._lock = threading.Lock()
        # (ts, phase, flops, hbm_bytes, live_tokens, ici_bytes)
        self._events: deque = deque()
        self._totals = {"prefill_tokens": 0, "decode_tokens": 0,
                        "flops": 0.0, "hbm_bytes": 0.0, "ici_bytes": 0.0,
                        "dispatches": 0}
        self._collective = {"all_reduce": 0.0, "all_gather": 0.0}
        # compile tracking
        self._compile_counts: dict = {}
        # the tracked programs' builds, newest last (`compile.recent`,
        # and with the untracked ones `builds` of /debug/perf)
        self._builds: deque = deque(maxlen=_EVENT_TAIL)
        self._build_seconds: dict = {}  # kind -> stage -> seconds
        self._cache_hits = self._cache_misses = 0
        self._stages = build_stages()
        self._compile_seconds = 0.0
        self._unexpected = 0
        self._steady = False
        # HBM occupancy (memory_stats poll; device 0 is the headline, the
        # per-device list shows whether a mesh shards evenly)
        self._hbm = {"used": 0, "total": 0, "peak": 0, "devices": []}
        self._hbm_ts = 0.0
        # anomaly subscription (engine/diagnostics.py): called OUTSIDE
        # self._lock with (trigger_name, detail_dict) when a bug signal
        # fires here — unexpected recompile, HBM past hbm_threshold. The
        # subscriber must return fast (DiagnosticsManager.trigger spawns
        # its capture thread and returns).
        self.anomaly_hook: Optional[Callable[[str, dict], None]] = None
        self.hbm_threshold = 0.0  # fraction of HBM; 0 = disabled
        # -- tenant attribution plane (production_stack_tpu/tenancy.py) --
        # Per-tenant cumulative counters, fed by the same record_* calls
        # that bill the fleet-wide window: every dispatch's wall seconds
        # split by each tenant's live-token share of the packed stream
        # (split_shares: parts sum to the dispatch's seconds bit-exactly,
        # so per-tenant chip-seconds conserve against the dispatch-seconds
        # total). Observe-only: disabling changes nothing outside
        # self._tenants / self._tenant_seconds — fleet totals and the
        # event window are bit-identical either way. Internally bounded:
        # past _tenant_cap the smallest records fold into "other" (sums
        # conserved), and every export folds again to tenant_top_k.
        self.tenant_metering = bool(tenant_metering)
        self.tenant_top_k = max(int(tenant_top_k), 1)
        self._tenant_cap = max(4 * self.tenant_top_k, 64)
        self._tenants: Dict[str, Dict[str, float]] = {}
        self._tenant_seconds = 0.0  # total attributed dispatch seconds
        # last-activity stamp per tenant: rows idle past tenant_idle_expiry
        # are dropped (their cumulative sums fold into "other" first, so
        # fleet totals conserve) — a month-long process doesn't pin every
        # tenant ever seen under the fold cap. 6h matches the router
        # tracker's bin horizon (router/slo.py _HORIZON).
        self.tenant_idle_expiry = 21600.0
        self._tenant_seen: Dict[str, float] = {}

    @classmethod
    def from_runner(cls, config, runner) -> "PerfAccountant":
        param_count = param_bytes = 0
        params = getattr(runner, "params", None)
        if params is not None:
            try:
                import jax

                leaves = jax.tree.leaves(params)
                param_count = sum(int(x.size) for x in leaves)
                param_bytes = sum(int(x.size) * x.dtype.itemsize
                                  for x in leaves)
            except Exception:
                param_count = param_bytes = 0
        if not param_count:
            param_count = estimate_param_count(config.model)
            param_bytes = param_count * _dtype_bytes(config.model.dtype)
        perf = config.perf
        # chip count from the runner's mesh; collective degree from the
        # resolved sharding rules — when the head axes fell back to
        # replication (geometry not divisible) the matmuls run locally
        # and there is nothing to all-reduce, whatever the mesh shape
        mesh = getattr(runner, "mesh", None)
        n_chips = int(mesh.devices.size) if mesh is not None else 1
        tensor_parallel = 1
        rules = getattr(runner, "rules", None)
        if mesh is not None and rules is not None:
            from production_stack_tpu.parallel import shardings as ln
            from production_stack_tpu.parallel.mesh import AXIS_TENSOR

            if rules.rules.get(ln.HEADS) is not None:
                tensor_parallel = int(mesh.shape[AXIS_TENSOR])
        # peaks: an explicit --perf-peak-* wins, else the table entry of
        # the device this runs on, else none (no utilization exported)
        import jax

        kind = jax.devices()[0].device_kind
        table = DEVICE_PEAKS.get(kind, (0.0, 0.0, 0.0))
        peaks = [explicit or known for explicit, known in zip(
            (perf.peak_tflops, perf.peak_hbm_gbps, perf.peak_ici_gbps),
            table)]
        if not all(peaks):
            _log.info(
                "no published peaks for device_kind %r: utilization gauges "
                "are not exported (set --perf-peak-tflops / "
                "--perf-peak-hbm-gbps / --perf-peak-ici-gbps)", kind)
        acct = cls(config.model, param_count=param_count,
                   param_bytes=param_bytes, window=perf.window,
                   peak_tflops=peaks[0],
                   peak_hbm_gbps=peaks[1],
                   hbm_poll_interval=perf.hbm_poll_interval,
                   n_chips=n_chips, tensor_parallel=tensor_parallel,
                   peak_ici_gbps=peaks[2],
                   tenant_metering=getattr(config, "tenant_metering", True),
                   tenant_top_k=getattr(config, "tenant_top_k", 8))
        return acct

    # -- compile events ------------------------------------------------------
    def on_compile(self, kind: str, bucket: str, seconds: float,
                   build: Optional[dict] = None) -> None:
        """One build of a tracked program (``CompileTracker``'s observer):
        ``build`` as ``new_build`` shapes it, or None from a caller that
        knows the first call's wall time alone (then all of it is
        `first_run`)."""
        if build is None:
            build = new_build(kind, bucket)
            build["seconds"] = build["stages"]["first_run"] = seconds
        with self._lock:
            key = (kind, bucket)
            self._compile_counts[key] = self._compile_counts.get(key, 0) + 1
            self._compile_seconds += seconds
            build["unexpected"] = self._steady
            if self._steady:
                self._unexpected += 1
            self._builds.append(build)
            by_stage = self._build_seconds.setdefault(
                kind, dict.fromkeys(BUILD_STAGES, 0.0))
            for stage, sec in build["stages"].items():
                by_stage[stage] += sec
            self._cache_hits += build["cache_hits"]
            self._cache_misses += build["cache_misses"]
        if build["unexpected"] and self.anomaly_hook is not None:
            self.anomaly_hook("unexpected_recompile", dict(build))

    def _build_fields(self) -> dict:
        """Seconds by kind and stage, programs built by kind and the
        persistent cache's answers, over the tracked programs' builds and
        the process's untracked ones (kind `other`); under the lock."""
        other = self._stages
        seconds = {kind: dict(by_stage)
                   for kind, by_stage in self._build_seconds.items()}
        seconds["other"] = dict(other.other_seconds)
        builds = {"other": other.other_builds}
        for (kind, _), n in self._compile_counts.items():
            builds[kind] = builds.get(kind, 0) + n
        return {"program_build_seconds": seconds,
                "program_builds": builds,
                "compile_cache_hits": self._cache_hits + other.other_hits,
                "compile_cache_misses": (self._cache_misses
                                         + other.other_misses)}

    def mark_steady(self) -> None:
        """Warmup pre-compiled every serving variant: from here on a fresh
        compile means a shape leaked past warmup — a bug signal."""
        with self._lock:
            self._steady = True

    # -- dispatch accounting -------------------------------------------------
    def _weight_bytes(self, tokens: int) -> float:
        """Weight bytes a dispatch over ``tokens`` live tokens reads."""
        if not self._expert_share:
            return self.param_bytes + self._loop_extra_bytes
        touched = 1.0 - (1.0 - self._route_share) ** max(tokens, 0)
        return self._loop_extra_bytes + self.param_bytes * (
            1.0 - self._expert_share * (1.0 - touched))

    def record_decode(self, live_seqs: int, steps: int, ctx_tokens: int,
                      ts: Optional[float] = None, *,
                      seconds: float = 0.0,
                      tenants: Optional[dict] = None) -> None:
        """One fused decode dispatch: ``steps`` iterations over
        ``live_seqs`` sequences with ``ctx_tokens`` total context. Decode
        re-reads the weights every step — the weight-bandwidth-bound
        regime of docs/roofline.md."""
        tokens = live_seqs * steps
        flops = (2.0 * self.active_param_count * tokens
                 + self._attn_per_tok_ctx * ctx_tokens * steps)
        hbm = steps * (self._weight_bytes(live_seqs)
                       + (ctx_tokens + live_seqs) * self._kv_bytes_per_tok)
        ar = tokens * self._ar_bytes_per_tok
        ag = tokens * self._ag_bytes_per_row
        self._record(ts, "decode", flops, hbm, tokens,
                     ar_bytes=ar, ag_bytes=ag)
        self.attribute_tenants(seconds, tenants)

    def record_ragged(self, prefill_tokens: int, prefill_ctx: int,
                      prefill_rows: int, decode_seqs: int, decode_ctx: int,
                      ts: Optional[float] = None, *,
                      spec_tokens: int = 0, spec_ctx: int = 0,
                      spec_rows: int = 0, seconds: float = 0.0,
                      tenants: Optional[dict] = None) -> None:
        """One unified ragged dispatch: ``prefill_tokens`` prompt tokens
        over ``prefill_rows`` chunks (post-chunk contexts summing to
        ``prefill_ctx``) packed together with ``decode_seqs`` single-token
        decode rows (contexts summing to ``decode_ctx``).

        The cost splits by the actual unpadded per-phase token counts and
        lands as TWO window events so the phase gauges
        (``vllm:tokens_per_second{phase}``) stay meaningful: the prefill
        share carries the weight pass (param_bytes read once per
        dispatch, attributed to whichever phase is present), the decode
        share adds its attention context FLOPs and KV traffic on top —
        one fused dispatch never double-counts the weight read.

        Speculative draft/verify spans (``spec_tokens`` draft tokens over
        ``spec_rows`` rows, post-span contexts summing to ``spec_ctx``)
        are prefill-SHAPED work and their FLOPs/KV traffic are costed
        into the prefill event — but with ZERO goodput tokens there:
        drafts only become goodput if accepted, and accepted tokens land
        as decode goodput via ``record_spec_accepted`` (each spec row's
        one guaranteed token is already in ``decode_seqs``).

        Collective (ICI) bytes ride the same split: every live token
        all-reduces its two row-parallel matmul outputs per layer, and
        every consumed-logits stream position (prefill samples, decode
        rows, verify columns) all-gathers its vocab-sharded logits row.
        Zero at tp=1 — the arithmetic, not a flag, turns it off.

        ``seconds`` (the fused dispatch's wall time) and ``tenants``
        (per-tenant ``{"prefill", "decode", "live"}`` token shares of the
        packed stream) feed the tenant attribution plane: the wall time
        splits by each tenant's live-token share with exact conservation
        — per-tenant chip-seconds sum to total dispatch seconds."""
        if prefill_tokens <= 0 and decode_seqs <= 0 and spec_tokens <= 0:
            return
        self.attribute_tenants(seconds, tenants)
        if prefill_tokens > 0 or spec_tokens > 0:
            ctx_mean = prefill_ctx / max(prefill_rows, 1)
            flops = (2.0 * self.active_param_count * prefill_tokens
                     + self._attn_per_tok_ctx * prefill_tokens * ctx_mean)
            hbm = (self._weight_bytes(prefill_tokens + spec_tokens
                                      + decode_seqs)
                   + (prefill_tokens + prefill_ctx) * self._kv_bytes_per_tok)
            if spec_tokens > 0:
                spec_ctx_mean = spec_ctx / max(spec_rows, 1)
                flops += (2.0 * self.active_param_count * spec_tokens
                          + self._attn_per_tok_ctx * spec_tokens
                          * spec_ctx_mean)
                hbm += ((spec_tokens + spec_ctx) * self._kv_bytes_per_tok)
            ar = (prefill_tokens + spec_tokens) * self._ar_bytes_per_tok
            ag = (prefill_rows + spec_tokens) * self._ag_bytes_per_row
            self._record(ts, "prefill", flops, hbm, prefill_tokens,
                         ar_bytes=ar, ag_bytes=ag)
        if decode_seqs > 0:
            flops = (2.0 * self.active_param_count * decode_seqs
                     + self._attn_per_tok_ctx * decode_ctx)
            hbm = (decode_ctx + decode_seqs) * self._kv_bytes_per_tok
            if prefill_tokens <= 0 and spec_tokens <= 0:
                # decode-only pays the weights
                hbm += self._weight_bytes(decode_seqs)
            ar = decode_seqs * self._ar_bytes_per_tok
            ag = decode_seqs * self._ag_bytes_per_row
            self._record(ts, "decode", flops, hbm, decode_seqs,
                         ar_bytes=ar, ag_bytes=ag)

    def record_spec_accepted(self, tokens: int,
                             ts: Optional[float] = None,
                             tenant: Optional[str] = None) -> None:
        """Accepted speculative tokens: pure decode goodput on top of the
        one-per-row the dispatch already counted. Zero FLOPs/HBM here —
        the verification work that produced them was costed as
        prefill-phase span work in ``record_ragged``. Not a dispatch."""
        if tokens <= 0:
            return
        now = ts if ts is not None else time.monotonic()
        with self._lock:
            self._events.append((now, "decode", 0.0, 0.0, tokens, 0.0))
            self._totals["decode_tokens"] += tokens
            self._trim(now)
        if tenant is not None:
            self.attribute_tenants(0.0, {tenant: {"decode": tokens}})

    # -- tenant attribution --------------------------------------------------
    def attribute_tenants(self, seconds: float,
                          tenants: Optional[dict]) -> None:
        """Bill one dispatch to its tenants: per-tenant prefill/decode
        goodput tokens accumulate directly, and the dispatch's wall
        ``seconds`` split by each tenant's ``live`` token share
        (tenancy.split_shares — parts sum to ``seconds`` bit-exactly, the
        conservation invariant). No-op when metering is off or the
        dispatch carried no tenant map."""
        if not self.tenant_metering or not tenants:
            return
        live = {t: rec.get("live", 0) for t, rec in tenants.items()
                if rec.get("live", 0) > 0}
        shares = split_shares(seconds, live) if seconds > 0 else {}
        now = time.monotonic()
        with self._lock:
            for t, rec in tenants.items():
                row = self._tenant_row(t)
                row["prefill_tokens"] += int(rec.get("prefill", 0))
                row["decode_tokens"] += int(rec.get("decode", 0))
                row["chip_seconds"] += shares.get(t, 0.0)
                self._tenant_seen[t] = now
            self._tenant_seconds += sum(shares.values())
            self._bound_tenants(now)

    def _tenant_row(self, tenant: str) -> dict:
        return self._tenants.setdefault(
            tenant, {"prefill_tokens": 0, "decode_tokens": 0,
                     "chip_seconds": 0.0, "requests": 0,
                     "queue_seconds_sum": 0.0})

    def _bound_tenants(self, now: Optional[float] = None) -> None:
        self.expire_idle_tenants(now, _locked=True)
        if len(self._tenants) > self._tenant_cap:
            # bound the *internal* table too, not just the export: fold
            # the smallest records into "other" (sums conserved)
            self._tenants = fold_records(
                self._tenants, k=self._tenant_cap // 2,
                weight_key="chip_seconds")
            self._tenant_seen = {t: ts for t, ts in
                                 self._tenant_seen.items()
                                 if t in self._tenants}

    def expire_idle_tenants(self, now: Optional[float] = None,
                            _locked: bool = False) -> int:
        """Fold tenants idle past ``tenant_idle_expiry`` (6h, the router
        tracker's bin horizon) into the ``"other"`` row. Cumulative sums
        conserve — only the identity is forgotten — and the cap slots
        recycle under identity churn instead of pinning every tenant
        ever seen for the life of the process. Returns the number
        expired."""
        now = now if now is not None else time.monotonic()
        if not _locked:
            with self._lock:
                return self.expire_idle_tenants(now, _locked=True)
        cutoff = now - self.tenant_idle_expiry
        stale = [t for t, ts in self._tenant_seen.items()
                 if ts < cutoff and t != OTHER]
        for t in stale:
            row = self._tenants.pop(t, None)
            self._tenant_seen.pop(t, None)
            if row:
                other = self._tenant_row(OTHER)
                for k, v in row.items():
                    other[k] = other.get(k, 0) + v
        return len(stale)

    def note_request(self, tenant: str, queue_seconds: float) -> None:
        """One finished request: per-tenant request count and queue-time
        (arrival → admission) accumulation — the source of
        ``vllm:tenant_queue_time_seconds``."""
        if not self.tenant_metering:
            return
        now = time.monotonic()
        with self._lock:
            row = self._tenant_row(tenant)
            row["requests"] += 1
            row["queue_seconds_sum"] += max(float(queue_seconds), 0.0)
            self._tenant_seen[tenant] = now
            self._bound_tenants(now)

    def attribute_seconds(self, tenant_live: dict,
                          seconds: float) -> None:
        """Attribute extra wall seconds (the deferred result fetch of a
        dispatch already billed) by the same live-token shares — keeps
        the conservation invariant across the dispatch/resolve split."""
        if seconds <= 0 or not tenant_live:
            return
        self.attribute_tenants(
            seconds, {t: {"live": n} for t, n in tenant_live.items()})

    def tenant_fields(self, kv_blocks: Optional[dict] = None) -> dict:
        """Bounded per-tenant export for ``stats()['tenants']`` and
        ``/debug/tenants``: cumulative records folded to the top-K by
        chip-seconds with the remainder under ``tenant="other"``
        (tenancy.fold_records — every field's fleet total survives the
        fold). ``kv_blocks`` is the engine's live per-tenant block count
        from the scheduler, merged here so one fold governs every
        export."""
        with self._lock:
            records = {t: dict(r) for t, r in self._tenants.items()}
            seconds_total = self._tenant_seconds
        for t, blocks in (kv_blocks or {}).items():
            rec = records.get(t)
            if rec is None:
                rec = records[t] = {
                    "prefill_tokens": 0, "decode_tokens": 0,
                    "chip_seconds": 0.0, "requests": 0,
                    "queue_seconds_sum": 0.0}
            rec["kv_blocks"] = int(blocks)
        folded = fold_records(records, k=self.tenant_top_k,
                              weight_key="chip_seconds")
        for row in folded.values():
            row.setdefault("kv_blocks", 0)
        return {
            "enabled": self.tenant_metering,
            "top_k": self.tenant_top_k,
            "tracked": len(records),
            "dispatch_seconds_total": seconds_total,
            "tenants": {t: folded[t] for t in sorted(folded)},
        }

    def _record(self, ts, phase, flops, hbm_bytes, tokens,
                ar_bytes: float = 0.0, ag_bytes: float = 0.0) -> None:
        now = ts if ts is not None else time.monotonic()
        ici = ar_bytes + ag_bytes
        with self._lock:
            self._events.append((now, phase, flops, hbm_bytes, tokens, ici))
            self._totals[f"{phase}_tokens"] += tokens
            self._totals["flops"] += flops
            self._totals["hbm_bytes"] += hbm_bytes
            self._totals["ici_bytes"] += ici
            self._collective["all_reduce"] += ar_bytes
            self._collective["all_gather"] += ag_bytes
            self._totals["dispatches"] += 1
            self._trim(now)

    def _trim(self, now: float) -> None:
        while self._events and self._events[0][0] < now - self.window:
            self._events.popleft()

    # -- HBM occupancy -------------------------------------------------------
    def poll_hbm(self, now: Optional[float] = None) -> None:
        now = now if now is not None else time.monotonic()
        if now - self._hbm_ts < self.hbm_poll_interval and self._hbm_ts:
            return
        self._hbm_ts = now
        import jax

        # the CPU backend reports no memory stats (None): gauges stay 0.
        # An accelerator that cannot answer is an error worth seeing.
        per_dev = [(d.id, d.memory_stats() or {})
                   for d in jax.local_devices()]
        stats = per_dev[0][1]
        used = int(stats.get("bytes_in_use", 0))
        total = int(stats.get("bytes_limit", 0))
        with self._lock:
            self._hbm["used"] = used
            self._hbm["total"] = total
            self._hbm["peak"] = max(self._hbm["peak"],
                                    int(stats.get("peak_bytes_in_use", used)))
            self._hbm["devices"] = [
                {"id": i, "bytes_in_use": int(st.get("bytes_in_use", 0)),
                 "bytes_limit": int(st.get("bytes_limit", 0))}
                for i, st in per_dev if st]
        if (self.anomaly_hook is not None and self.hbm_threshold > 0
                and total > 0 and used / total >= self.hbm_threshold):
            self.anomaly_hook("hbm_pressure", {
                "used_bytes": used, "total_bytes": total,
                "fraction": round(used / total, 4),
                "threshold": self.hbm_threshold,
            })

    # -- reductions ----------------------------------------------------------
    def _window_rates(self, now: float) -> dict:
        self._trim(now)
        flops = hbm = ici = ptok = dtok = 0.0
        span = 1.0
        if self._events:
            span = max(now - self._events[0][0], 1e-3)
            flops = sum(e[2] for e in self._events)
            hbm = sum(e[3] for e in self._events)
            ptok = sum(e[4] for e in self._events if e[1] == "prefill")
            dtok = sum(e[4] for e in self._events if e[1] == "decode")
            ici = sum(e[5] for e in self._events)
        return {
            # None where the device's peak is unknown (no utilization)
            "mfu": _ratio(flops / span, self.peak_flops),
            "hbm_bw_util": _ratio(hbm / span, self.peak_hbm),
            "ici_bw_util": _ratio(ici / span, self.peak_ici),
            "flops_per_s": flops / span,
            "hbm_bytes_per_s": hbm / span,
            "ici_bytes_per_s": ici / span,
            "prefill_tps": ptok / span,
            "decode_tps": dtok / span,
        }

    def stats_fields(self) -> dict:
        """Flat fields merged into ``LLMEngine.stats()`` for the metrics
        collector (engine/metrics.py reads this at scrape time)."""
        self.poll_hbm()
        now = time.monotonic()
        with self._lock:
            rates = self._window_rates(now)
            return {
                **rates,
                "chips": self.n_chips,
                "collective_bytes": dict(self._collective),
                "hbm_bytes_used": self._hbm["used"],
                "hbm_bytes_total": self._hbm["total"],
                "hbm_bytes_peak": self._hbm["peak"],
                "compile_counts": dict(self._compile_counts),
                "compile_seconds_total": self._compile_seconds,
                "unexpected_recompiles": self._unexpected,
                "dispatches_total": self._totals["dispatches"],
                **self._build_fields(),
            }

    def snapshot(self) -> dict:
        """JSON document for ``GET /debug/perf``."""
        self.poll_hbm()
        now = time.monotonic()
        with self._lock:
            rates = self._window_rates(now)
            # per-axis roofline breakdown: achieved window rate against
            # each ceiling, side by side, so /debug/perf shows WHICH wall
            # a multi-chip engine is against (flop/hbm aggregate over the
            # mesh; ici per chip — see __init__)
            rooflines = {
                "flop": {"peak_per_s": self.peak_flops or None,
                         "achieved_per_s": rates["flops_per_s"],
                         "utilization": rates["mfu"]},
                "hbm": {"peak_per_s": self.peak_hbm or None,
                        "achieved_per_s": rates["hbm_bytes_per_s"],
                        "utilization": rates["hbm_bw_util"]},
                "ici": {"peak_per_s": self.peak_ici or None,
                        "achieved_per_s": rates["ici_bytes_per_s"],
                        "utilization": rates["ici_bw_util"]},
            }
            return {
                "enabled": True,
                "window_seconds": self.window,
                "chips": self.n_chips,
                "tensor_parallel": self.tp,
                "peaks": {"flops": self.peak_flops,
                          "hbm_bytes_per_s": self.peak_hbm,
                          "ici_bytes_per_s": self.peak_ici},
                "model": {"param_count": self.param_count,
                          "param_bytes": self.param_bytes,
                          "loop_passes": self.loop_passes,
                          "cache_layers": self.cache_layers,
                          "kv_bytes_per_token": self._kv_bytes_per_tok},
                "model_flops_utilization": rates["mfu"],
                "hbm_bandwidth_utilization": rates["hbm_bw_util"],
                "ici_bandwidth_utilization": rates["ici_bw_util"],
                "rooflines": rooflines,
                "collective_bytes_total": dict(self._collective),
                "tokens_per_second": {"prefill": rates["prefill_tps"],
                                      "decode": rates["decode_tps"]},
                "hbm_bytes": dict(self._hbm),
                "totals": dict(self._totals),
                "compile": {
                    "steady": self._steady,
                    "total_events": sum(self._compile_counts.values()),
                    "total_seconds": round(self._compile_seconds, 4),
                    "unexpected_recompiles": self._unexpected,
                    "counts": {f"{k}:{b}": n for (k, b), n
                               in sorted(self._compile_counts.items())},
                    "recent": list(self._builds),
                },
                # every build, the untracked programs' (`other`) first
                "builds": [*self._stages.other, *self._builds],
            }
