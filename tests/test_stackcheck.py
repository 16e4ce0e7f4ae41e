"""Tier-1 guard for tools/stackcheck: the nine passes detect their
fixture positives (and stay silent on the negatives), suppressions and
the baseline round-trip, the --json shape is stable, --changed filters
to git-touched files without unsoundness, and — the gate that matters —
the real repo runs clean.

The fixture mini-repo lives in tests/stackcheck_fixtures/ (see its
README); fixture files and the expectations here are updated together.
"""

import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "stackcheck_fixtures"
sys.path.insert(0, str(REPO))

from tools.stackcheck import core  # noqa: E402


def fixture_report(only=None, baseline=None):
    return core.run_passes(FIXTURES, only=only, baseline_path=baseline)


def by_file(report, name):
    """All findings (any status) whose path ends with name."""
    return [f for f in report.findings if f.path.endswith(name)]


# ---- registry / framework -------------------------------------------------

def test_all_nine_passes_registered():
    assert sorted(core.all_passes()) == [
        "async-blocking", "config-drift", "http-surface-drift",
        "jit-cache-hygiene", "jit-purity", "lock-across-await",
        "lock-discipline", "metric-hygiene", "task-lifetime",
    ]


# ---- async-blocking -------------------------------------------------------

def test_async_blocking_positives():
    r = fixture_report(only="async-blocking")
    msgs = sorted(f.message for f in by_file(r, "bad_async.py"))
    assert len(msgs) == 8, msgs
    joined = "\n".join(msgs)
    assert "time.sleep() blocks the event loop" in joined
    assert "sync HTTP (requests) blocks the event loop" in joined
    assert "subprocess blocks the event loop" in joined
    assert "sync file IO blocks the event loop" in joined
    assert "queue.get() blocks the event loop" in joined
    assert "Thread.join() blocks the event loop" in joined
    assert "sync HTTP (requests.get) in async-tier module" in joined
    assert "busy-wait time.sleep loop" in joined


def test_async_blocking_negatives():
    r = fixture_report(only="async-blocking")
    assert by_file(r, "good_async.py") == []


def test_comment_block_suppression():
    r = fixture_report(only="async-blocking")
    found = by_file(r, "suppressed_async.py")
    assert len(found) == 1
    assert found[0] in r.suppressed
    assert found[0] not in r.active


# ---- lock-across-await ----------------------------------------------------

def test_lock_across_await():
    r = fixture_report(only="lock-across-await")
    found = by_file(r, "locks_fixture.py")
    assert sorted(f.message.split(":")[0] for f in found) == [
        "in async def bad_hold", "in async def bad_inline"]
    assert r.findings == found  # nothing elsewhere in the fixtures


# ---- jit-purity -----------------------------------------------------------

def test_jit_purity():
    r = fixture_report(only="jit-purity")
    found = by_file(r, "kernels_fixture.py")
    assert r.findings == found
    msgs = "\n".join(f.message for f in found)
    bad = [f for f in found if "in jitted bad_kernel" in f.message]
    assert len(bad) == 5, msgs
    assert "print() traces to nothing" in msgs
    assert "np.random.rand() is host-side RNG" in msgs
    assert "time.time() bakes a host clock read" in msgs
    assert ".item() forces a device" in msgs
    assert "float() on traced argument 'x'" in msgs
    assert sum("unhashable list default" in f.message for f in found) == 1
    assert sum("in jitted <lambda>" in f.message for f in found) == 1
    assert not any("good_kernel" in f.message for f in found)
    assert not any("host_helper" in f.message for f in found)


# ---- jit-cache-hygiene ----------------------------------------------------

def test_jit_cache_hygiene_positives():
    r = fixture_report(only="jit-cache-hygiene")
    found = by_file(r, "jit_cache_fixture.py")
    assert r.findings == found  # nothing elsewhere in the fixtures
    active = [f for f in found if f in r.active]
    msgs = "\n".join(f.message for f in active)
    assert len(active) == 5, msgs
    # rule A: body-local wrapper construction — the PR 13 repro plus a
    # nested @jax.jit decoration
    ctor = [f for f in active if "fresh jax.jit wrapper" in f.message]
    assert len(ctor) == 2
    assert "export_fresh()" in msgs and "nested_decorated()" in msgs
    assert "every call recompiles" in msgs
    # rule B: unhashable literal at a registered wrapper's static slot
    assert "unhashable list literal in static arg position 1" in msgs
    # rule C: shape/len-derived value at a static slot
    assert "shape/len-derived value in static arg position 1" in msgs
    # rule D: shape-dependent branch + dynamically-sliced operand
    assert ("shape-dependent branch feeds jitted _bucketed_jit a "
            "dynamically-sliced operand" in msgs)


def test_jit_cache_hygiene_negatives_and_suppression():
    r = fixture_report(only="jit-cache-hygiene")
    msgs = "\n".join(f.message for f in r.active)
    # every caching idiom stays silent: module-level wrapper, __init__,
    # cached_property, self-memo (incl. chained assign), memo-dict on a
    # self-bound local, self-container append, hashable static call
    for neg in ("in __init__()", "_encode", "_io_fns", "_range_fns",
                "_compile_steps", "call_bucketed_ok"):
        assert neg not in msgs, neg
    sup = [f for f in r.suppressed if f.path.endswith("jit_cache_fixture.py")]
    assert len(sup) == 1
    assert "export_suppressed()" in sup[0].message


def test_jit_cache_hygiene_model_runner_memo_is_clean():
    """Acceptance pin: the real repo's model_runner._io_fns self-memo —
    the *fix* for the PR 13 fresh-wrapper bug — must not be flagged,
    while the pass stays active on the repo (zero active findings)."""
    rep = core.run_passes(REPO, only="jit-cache-hygiene")
    assert rep.active == [], "\n".join(f.render() for f in rep.active)
    assert not any("_io_fns" in f.message for f in rep.findings)


# ---- task-lifetime --------------------------------------------------------

def test_task_lifetime_positives():
    r = fixture_report(only="task-lifetime")
    found = by_file(r, "task_fixture.py")
    assert r.findings == found
    active = [f for f in found if f in r.active]
    msgs = "\n".join(f.message for f in active)
    assert len(active) == 5, msgs
    assert "create_task() result dropped" in msgs
    assert "ensure_future() handle bound to 't' but never read" in msgs
    assert sum("Executor.submit() future" in f.message
               for f in active) == 2
    assert "broad except with empty body in a serving-tier module" in msgs


def test_task_lifetime_negatives_and_suppression():
    r = fixture_report(only="task-lifetime")
    found = by_file(r, "task_fixture.py")
    # kept-set spawn, awaited future, observed submit, logging handler
    # and narrow except all stay silent — only the designed lines fire
    active_lines = sorted(f.line for f in found if f in r.active)
    assert len(active_lines) == 5
    sup = [f for f in found if f in r.suppressed]
    assert len(sup) == 1
    assert "broad except" in sup[0].message


# ---- lock-discipline ------------------------------------------------------

def test_lock_discipline_positives():
    r = fixture_report(only="lock-discipline")
    found = by_file(r, "guarded_fixture.py")
    assert r.findings == found
    active = [f for f in found if f in r.active]
    msgs = "\n".join(f.message for f in active)
    assert len(active) == 4, msgs
    assert ".append() write to self._items" in msgs
    assert sum("write to self._count" in f.message for f in active) == 2
    assert "bad_subscript" in msgs
    assert "guarded-by: _lock" in msgs


def test_lock_discipline_negatives_and_suppression():
    r = fixture_report(only="lock-discipline")
    msgs = "\n".join(f.message for f in r.active)
    # with-lock writes, nested with, the holds-lock helper, __init__
    # and the unannotated attribute all stay silent
    for neg in ("good_locked", "good_nested", "good_held_helper",
                "good_unannotated", "__init__", "_free"):
        assert neg not in msgs, neg
    sup = [f for f in r.suppressed if f.path.endswith("guarded_fixture.py")]
    assert len(sup) == 1
    assert "suppressed_write" in sup[0].message


# ---- http-surface-drift ---------------------------------------------------

def test_http_surface_drift_both_directions():
    r = fixture_report(only="http-surface-drift")
    msgs = "\n".join(f"{f.path}: {f.message}" for f in r.findings)
    assert len(r.findings) == 4, msgs
    # direction 1: documented/referenced but never registered
    assert ("documents endpoint /debug/fixture_ghost but no server "
            "module registers that route" in msgs)
    assert "client hits /debug/fixture_missing" in msgs
    assert "probe path /readyz is not registered" in msgs
    # direction 2: registered but undocumented
    assert ("registers /debug/fixture_undocumented but no doc "
            "mentions it" in msgs)
    # negatives: the documented route, the loop-constant /v1 routes,
    # the templated route, the live client path, and the real probe +
    # preStop paths all stay silent
    assert "fixture_dash" not in msgs
    assert "fixture_echo" not in msgs and "fixture_stream" not in msgs
    assert "fixture_bundles" not in msgs
    assert "/drain" not in msgs
    assert "path /health" not in msgs
    assert "probe path /ready " not in msgs


# ---- config-drift ---------------------------------------------------------

def test_config_drift():
    r = fixture_report(only="config-drift")
    msgs = "\n".join(f"{f.path}: {f.message}" for f in r.findings)
    assert len(r.findings) == 6, msgs
    assert "renders '--bogus-flag' for fixturepkg.app" in msgs
    assert "'fixturepkg.missing' has no source file" in msgs
    assert "engineConfig.ghostKnob is dead config" in msgs
    assert "routerSpec.deadScalar is dead config" in msgs
    assert "routerSpec.resilience.ghostResilience is dead config" in msgs
    assert "overlay key routerSpec.typoScalar does not exist" in msgs
    # negatives: consumed keys and real flags stay silent
    for ok in ("maxModelLen", "replicaCount", "circuitBreaker",
               "attentionImpl", "--host", "--max-model-len",
               "--attention-impl"):
        assert ok not in msgs


# ---- metric-hygiene -------------------------------------------------------

def test_metric_hygiene():
    r = fixture_report(only="metric-hygiene")
    msgs = "\n".join(f"{f.path}: {f.message}" for f in r.findings)
    assert len(r.findings) == 7, msgs
    assert "references 'vllm:fixture_dashboard_ghost', not defined" in msgs
    # rule files: recorded names count as defined, ghost exprs do not
    assert "references 'vllm:fixture_rule_ghost', not defined" in msgs
    assert "fixture_recorded" not in msgs
    assert "documents 'vllm:fixture_ghost', not defined" in msgs
    assert "missing 'vllm:fixture_undocumented'" in msgs
    assert "label 'request_id' looks per-request" in msgs
    assert "already registered on the default registry" in msgs
    # the registry=... constructor is exempt from duplicate checking
    assert sum("already registered" in f.message for f in r.findings) == 1
    # identity label with no fold helper in the file is a finding; the
    # _ok_ fixture declares the same label but references fold_top_k
    assert ("tenant_metrics_fixture.py: metric 'router:fixture_tenant_queue'"
            " label 'tenant' is free-form identity" in msgs)
    assert "fixture_tenant_folded" not in msgs


# ---- baseline round-trip --------------------------------------------------

def test_baseline_roundtrip(tmp_path):
    first = fixture_report()
    assert first.active and first.suppressed
    bl = tmp_path / "baseline.json"
    core.write_baseline(bl, first.active)

    second = fixture_report(baseline=bl)
    assert second.active == []
    assert len(second.baselined) == len(first.active)
    # suppressed findings stay suppressed, never baselined
    assert len(second.suppressed) == len(first.suppressed)


def test_baseline_is_line_free(tmp_path):
    f = core.Finding("async-blocking", "a/b.py", 42, "msg")
    assert "42" not in f.baseline_key
    bl = tmp_path / "baseline.json"
    core.write_baseline(bl, [f])
    assert core.load_baseline(bl) == {"async-blocking a/b.py msg"}


# ---- JSON shape -----------------------------------------------------------

def test_json_report_is_stable():
    a = fixture_report().to_json()
    b = fixture_report().to_json()
    assert json.dumps(a) == json.dumps(b)
    assert a["version"] == 1
    assert a["passes"] == sorted(core.all_passes())
    assert set(a["counts"]) == {"active", "suppressed", "baselined"}
    rows = a["findings"]
    assert rows == sorted(
        rows, key=lambda r: (r["path"], r["line"], r["pass"], r["message"]))
    for row in rows:
        assert list(row) == ["pass", "path", "line", "message", "status"]
        assert row["status"] in ("active", "suppressed", "baselined")


# ---- CLI ------------------------------------------------------------------

def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "tools.stackcheck", *args],
        capture_output=True, text=True, cwd=REPO)


def test_cli_repo_runs_clean():
    proc = _cli()
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_fixture_json_and_exit_code():
    proc = _cli("--root", str(FIXTURES), "--json")
    assert proc.returncode == 1
    data = json.loads(proc.stdout)
    assert data["counts"]["active"] > 0


def test_cli_unknown_pass():
    proc = _cli("--pass", "no-such-pass")
    assert proc.returncode == 2
    assert "unknown pass" in proc.stderr


def test_cli_list():
    proc = _cli("--list")
    assert proc.returncode == 0
    for name in core.all_passes():
        assert name in proc.stdout
    # stable ordering: rows come out in sorted-pass-name order
    rows = [ln.split()[0] for ln in proc.stdout.splitlines() if ln.strip()]
    assert rows == sorted(core.all_passes())


def test_cli_changed_filters_but_passes_stay_sound(tmp_path):
    """--changed reports only findings in git-touched files, while the
    passes still analyse the full tree (so cross-file checks see
    everything)."""
    git = ["git", "-C", str(tmp_path)]
    env_git = git + ["-c", "user.email=s@t", "-c", "user.name=s"]
    pkg = tmp_path / "production_stack_tpu" / "engine"
    pkg.mkdir(parents=True)
    bad = ("import asyncio\n\n\n"
           "async def spawn():\n"
           "    asyncio.create_task(spawn())\n")
    (pkg / "committed.py").write_text(bad)
    subprocess.run(git + ["init", "-q"], check=True)
    subprocess.run(git + ["add", "-A"], check=True)
    subprocess.run(env_git + ["commit", "-qm", "seed"], check=True)

    # clean tree: the committed finding exists but is filtered out
    proc = _cli("--root", str(tmp_path), "--changed")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 active" in proc.stdout

    # an untracked file with the same bug IS reported; the committed
    # one stays filtered even though the pass saw it
    (pkg / "fresh.py").write_text(bad)
    proc = _cli("--root", str(tmp_path), "--changed")
    assert proc.returncode == 1
    assert "fresh.py" in proc.stdout
    assert "committed.py" not in proc.stdout

    # explicit REF argument: everything since the empty-ish base commit
    subprocess.run(git + ["add", "-A"], check=True)
    subprocess.run(env_git + ["commit", "-qm", "more"], check=True)
    proc = _cli("--root", str(tmp_path), "--changed", "HEAD~1")
    assert proc.returncode == 1
    assert "fresh.py" in proc.stdout and "committed.py" not in proc.stdout


def test_cli_changed_outside_git_falls_back_to_full_run(tmp_path):
    pkg = tmp_path / "production_stack_tpu" / "engine"
    pkg.mkdir(parents=True)
    (pkg / "mod.py").write_text(
        "import asyncio\n\n\n"
        "async def spawn():\n"
        "    asyncio.create_task(spawn())\n")
    env = {"GIT_CEILING_DIRECTORIES": str(tmp_path)}
    proc = subprocess.run(
        [sys.executable, "-m", "tools.stackcheck", "--root", str(tmp_path),
         "--changed"],
        capture_output=True, text=True, cwd=REPO,
        env={**__import__("os").environ, **env})
    assert "running on the full tree" in proc.stderr
    assert proc.returncode == 1
    assert "mod.py" in proc.stdout


# ---- the gate: this repo is clean -----------------------------------------

def test_lifecycle_surface_is_inside_the_gates():
    """The drain/watchdog/resume surface is covered by the gates, not
    grandfathered around them: config-drift sees the chart's new flags
    as declared CLI flags (so a template typo would be an active
    finding), and metric-hygiene tracks the lifecycle metrics as both
    defined in code and documented (so deleting a docs/observability.md
    row would fail test_repo_has_no_active_findings)."""
    from tools.stackcheck.passes import config_drift, metric_hygiene

    ctx = core.Context(REPO)
    engine_flags = config_drift._parser_flags(
        ctx, REPO / "production_stack_tpu" / "engine" / "server.py")
    assert {"--drain-deadline", "--watchdog-stall-seconds"} <= engine_flags
    router_flags = config_drift._parser_flags(
        ctx, REPO / "production_stack_tpu" / "router" / "app.py")
    assert {"--no-stream-resume",
            "--health-check-failure-threshold"} <= router_flags

    lifecycle = {"vllm:drain_state", "vllm:drain_rejected_requests",
                 "vllm:drain_aborted_seqs", "vllm:watchdog_stalled",
                 "vllm:watchdog_stalls", "vllm:stream_resumes"}
    defined = metric_hygiene.code_metrics(ctx)
    assert lifecycle <= defined
    documented = metric_hygiene.doc_refs(ctx)
    assert lifecycle <= documented


def test_autoscaler_surface_is_inside_the_gates():
    """The autoscaling surface (PR: SLO-driven autoscaler) is covered by
    the gates, not grandfathered: config-drift sees the chart's
    --scale-* flags as declared router CLI flags (a
    routerSpec.scaleAdvisor template typo would be an active finding),
    and metric-hygiene tracks the autoscaler metrics as both defined in
    code and documented in docs/observability.md — so renaming one in
    code, or deleting its docs row or dashboard panel, fails
    test_repo_has_no_active_findings."""
    from tools.stackcheck.passes import config_drift, metric_hygiene

    ctx = core.Context(REPO)
    router_flags = config_drift._parser_flags(
        ctx, REPO / "production_stack_tpu" / "router" / "app.py")
    assert {"--scale-advisor", "--scale-min-replicas",
            "--scale-max-replicas", "--scale-target-queue",
            "--scale-kv-high", "--scale-burn-high",
            "--scale-down-fraction", "--scale-down-stable",
            "--scale-up-cooldown", "--scale-down-cooldown",
            "--scale-interval"} <= router_flags

    autoscaler = {"vllm:autoscaler_desired_replicas",
                  "vllm:autoscaler_scale_events",
                  "vllm:autoscaler_replica_hours",
                  "vllm:replica_warmup_seconds",
                  "vllm:engine_warming", "vllm:engine_warmup_seconds"}
    defined = metric_hygiene.code_metrics(ctx)
    assert autoscaler <= defined
    documented = metric_hygiene.doc_refs(ctx)
    assert autoscaler <= documented

    # the chart's autoscaling.mode toggle + scaleAdvisor block must stay
    # consumed by templates (the values-consumed gate keys off this)
    values = (REPO / "helm" / "values.yaml").read_text()
    assert "mode: keda" in values and "scaleAdvisor:" in values
    scaled = (REPO / "helm" / "templates"
              / "scaledobject-engine.yaml").read_text()
    assert "autoscaling.mode" in scaled


def test_diagnostics_surface_is_inside_the_gates():
    """The diagnostics/incidents surface (PR: anomaly-triggered bundles
    + fleet plane) is covered by the gates, not grandfathered:
    config-drift sees both tiers' --diagnostics-* flags as declared CLI
    flags (a routerSpec.diagnostics / engineConfig.diagnostics* template
    typo would be an active finding), and metric-hygiene tracks the
    diagnostic metric families as both defined in code and documented —
    so renaming one, or deleting its docs row or dashboard panel, fails
    test_repo_has_no_active_findings."""
    from tools.stackcheck.passes import config_drift, metric_hygiene

    ctx = core.Context(REPO)
    shared = {"--no-diagnostics", "--diagnostics-dir",
              "--diagnostics-max-bundles", "--diagnostics-max-bytes",
              "--diagnostics-cooldown"}
    engine_flags = config_drift._parser_flags(
        ctx, REPO / "production_stack_tpu" / "engine" / "server.py")
    assert shared | {"--diagnostics-profile-seconds",
                     "--diagnostics-hbm-threshold"} <= engine_flags
    router_flags = config_drift._parser_flags(
        ctx, REPO / "production_stack_tpu" / "router" / "app.py")
    assert shared | {"--diagnostics-interval"} <= router_flags

    families = {"vllm:diagnostic_bundles",
                "vllm:diagnostic_bundles_dropped",
                "vllm:diagnostic_capture_seconds",
                "vllm:incidents_open"}
    defined = metric_hygiene.code_metrics(ctx)
    assert families <= defined
    documented = metric_hygiene.doc_refs(ctx)
    assert families <= documented

    # both the routerSpec.diagnostics block and the per-model
    # engineConfig keys must stay consumed by the deployment templates
    # (the values-consumed gate keys off their presence in values.yaml)
    values = (REPO / "helm" / "values.yaml").read_text()
    assert "diagnostics:" in values and "diagnosticsMaxBundles:" in values
    assert "advisorTrigger:" in values
    router_tmpl = (REPO / "helm" / "templates"
                   / "deployment-router.yaml").read_text()
    assert "routerSpec.diagnostics" in router_tmpl


def test_multichip_surface_is_inside_the_gates():
    """The multi-chip surface (PR: sharded ragged dispatch + ICI
    roofline) is covered by the gates, not grandfathered: config-drift
    sees --tensor-parallel-size / --perf-peak-ici-gbps as declared
    engine CLI flags (an engineConfig.tensorParallelSize template typo
    would be an active finding), and metric-hygiene tracks the ICI
    metric families as both defined in code and documented — so
    renaming one, or deleting its docs row or dashboard panel, fails
    test_repo_has_no_active_findings."""
    from tools.stackcheck.passes import config_drift, metric_hygiene

    ctx = core.Context(REPO)
    engine_flags = config_drift._parser_flags(
        ctx, REPO / "production_stack_tpu" / "engine" / "server.py")
    assert {"--tensor-parallel-size", "--perf-peak-ici-gbps"} <= engine_flags

    # the counter is exposed as vllm:collective_bytes_total; the gate
    # pins the base family name (exposition adds the _total suffix)
    ici = {"vllm:ici_bandwidth_utilization", "vllm:collective_bytes"}
    defined = metric_hygiene.code_metrics(ctx)
    assert ici <= defined
    documented = metric_hygiene.doc_refs(ctx)
    assert ici <= documented

    # the chart's per-model TP knob and ICI peak override must stay
    # consumed by the engine deployment template (the values-consumed
    # gate keys off their presence in values.yaml)
    values = (REPO / "helm" / "values.yaml").read_text()
    assert "tensorParallelSize:" in values and "perfPeakIciGbps:" in values
    engine_tmpl = (REPO / "helm" / "templates"
                   / "deployment-engine.yaml").read_text()
    assert "tensorParallelSize" in engine_tmpl
    assert "perfPeakIciGbps" in engine_tmpl


def test_disagg_surface_is_inside_the_gates():
    """The disaggregation surface (PR: engine roles + streamed P→D KV
    handoff) is covered by the gates, not grandfathered: config-drift
    sees the role/transfer flags as declared CLI flags (an
    engineConfig.roles / kvTransfer* template typo would be an active
    finding), and metric-hygiene tracks the transfer metric families as
    both defined in code and documented — so renaming one, or deleting
    its docs/observability.md row, fails
    test_repo_has_no_active_findings."""
    from tools.stackcheck.passes import config_drift, metric_hygiene

    ctx = core.Context(REPO)
    engine_flags = config_drift._parser_flags(
        ctx, REPO / "production_stack_tpu" / "engine" / "server.py")
    assert {"--role", "--kv-transfer-group-layers", "--kv-transfer-window",
            "--kv-transfer-retries", "--kv-transfer-ttl"} <= engine_flags
    router_flags = config_drift._parser_flags(
        ctx, REPO / "production_stack_tpu" / "router" / "app.py")
    assert {"--static-backend-roles"} <= router_flags

    # exposition adds _total to the counters; the gate pins base names
    disagg = {"vllm:kv_transfer_bytes", "vllm:kv_transfer_seconds",
              "vllm:spliced_seqs", "vllm:disagg_requests"}
    defined = metric_hygiene.code_metrics(ctx)
    assert disagg <= defined
    documented = metric_hygiene.doc_refs(ctx)
    assert disagg <= documented

    # the chart's two-pool roles block must stay consumed by the engine
    # deployment template, and the CI values must exercise it (the
    # tier-1 chart tests render values-ci.yaml)
    values = (REPO / "helm" / "values.yaml").read_text()
    assert "roles:" in values
    values_ci = (REPO / "helm" / "values-ci.yaml").read_text()
    assert "roles:" in values_ci and "kvTransferWindow:" in values_ci
    engine_tmpl = (REPO / "helm" / "templates"
                   / "deployment-engine.yaml").read_text()
    assert "--role" in engine_tmpl and "stack/role" in (
        REPO / "helm" / "templates" / "_helpers.tpl").read_text()
    assert "kvTransferWindow" in engine_tmpl


def test_kv_tiering_surface_is_inside_the_gates():
    """The tiered-KV surface (PR: host/remote tiers + async prefetch +
    tier-aware routing) is covered by the gates, not grandfathered:
    config-drift sees the tiering flags as declared CLI flags (a helm
    kvTiering template typo would be an active finding), and
    metric-hygiene tracks both tier metric families as defined in code
    AND documented — so renaming vllm:kv_tier_hit_ratio, or deleting its
    docs/observability.md row, fails test_repo_has_no_active_findings."""
    from tools.stackcheck.passes import config_drift, metric_hygiene

    ctx = core.Context(REPO)
    engine_flags = config_drift._parser_flags(
        ctx, REPO / "production_stack_tpu" / "engine" / "server.py")
    assert {"--kv-host-cache-bytes", "--kv-prefetch-workers",
            "--host-offload-blocks", "--remote-kv-url"} <= engine_flags
    kvsrv_flags = config_drift._parser_flags(
        ctx, REPO / "production_stack_tpu" / "kv_server.py")
    assert {"--capacity-blocks", "--max-block-bytes",
            "--ttl-seconds"} <= kvsrv_flags

    # exposition adds _total to the counter; the gate pins base names
    tiering = {"vllm:kv_tier_hit_ratio", "vllm:kv_tier_bytes",
               "vllm:kv_prefetch_seconds",
               "vllm:kv_prefetch_overlap_fraction"}
    defined = metric_hygiene.code_metrics(ctx)
    assert tiering <= defined
    documented = metric_hygiene.doc_refs(ctx)
    assert tiering <= documented

    # the chart's kvTiering block must stay consumed by the engine
    # deployment template, the cache-server hardening knobs by its
    # template, and the CI values must exercise the host tier (the
    # tier-1 chart tests render values-ci.yaml)
    values = (REPO / "helm" / "values.yaml").read_text()
    assert "kvTiering:" in values and "maxBlockBytes:" in values
    values_ci = (REPO / "helm" / "values-ci.yaml").read_text()
    assert "kvTiering:" in values_ci and "hostCacheBytes:" in values_ci
    engine_tmpl = (REPO / "helm" / "templates"
                   / "deployment-engine.yaml").read_text()
    assert ("--kv-host-cache-bytes" in engine_tmpl
            and "--kv-prefetch-workers" in engine_tmpl)
    cs_tmpl = (REPO / "helm" / "templates"
               / "deployment-cache-server.yaml").read_text()
    assert "--max-block-bytes" in cs_tmpl and "--ttl-seconds" in cs_tmpl


def test_overload_surface_is_inside_the_gates():
    """The overload-protection surface (PR: per-tenant quotas + scheduler
    fair-share + staged brownout) is covered by the gates, not
    grandfathered: config-drift sees the quota/fairness/brownout flags as
    declared CLI flags on BOTH tiers (a helm tenancy/brownout template
    typo would be an active finding), and metric-hygiene tracks the
    overload metric families as defined in code and documented — so
    renaming vllm:brownout_stage, or deleting its docs/observability.md
    row, fails test_repo_has_no_active_findings."""
    from tools.stackcheck.passes import config_drift, metric_hygiene

    ctx = core.Context(REPO)
    engine_flags = config_drift._parser_flags(
        ctx, REPO / "production_stack_tpu" / "engine" / "server.py")
    assert {"--fair-share", "--tenant-weights", "--brownout",
            "--brownout-interval", "--brownout-queue-high",
            "--brownout-hbm-high", "--brownout-up-evals",
            "--brownout-calm-evals",
            "--brownout-max-tokens-clamp"} <= engine_flags
    router_flags = config_drift._parser_flags(
        ctx, REPO / "production_stack_tpu" / "router" / "app.py")
    assert {"--tenant-quota-config", "--brownout", "--brownout-interval",
            "--brownout-queue-depth", "--brownout-queue-high",
            "--brownout-up-evals", "--brownout-calm-evals"} <= router_flags

    # exposition adds _total to the counters; the gate pins base names
    overload = {"vllm:brownout_stage", "vllm:brownout_sheds",
                "vllm:quota_rejections", "vllm:fair_share_deficit"}
    defined = metric_hygiene.code_metrics(ctx)
    assert overload <= defined
    documented = metric_hygiene.doc_refs(ctx)
    assert overload <= documented

    # the chart's tenancy/brownout blocks must stay consumed by both
    # deployment templates, and the CI values must exercise fair-share +
    # quotas + the brownout ladder (the tier-1 chart tests render
    # values-ci.yaml)
    values = (REPO / "helm" / "values.yaml").read_text()
    assert ("fairShare:" in values and "tenantWeights:" in values
            and "quotas:" in values and "brownout:" in values)
    values_ci = (REPO / "helm" / "values-ci.yaml").read_text()
    assert ("fairShare: true" in values_ci and "quotas:" in values_ci
            and "brownout:" in values_ci)
    router_tmpl = (REPO / "helm" / "templates"
                   / "deployment-router.yaml").read_text()
    assert ("--tenant-quota-config" in router_tmpl
            and "--brownout" in router_tmpl)
    engine_tmpl = (REPO / "helm" / "templates"
                   / "deployment-engine.yaml").read_text()
    assert "--fair-share" in engine_tmpl and "--brownout" in engine_tmpl

    # the sustained-brownout alert rides the same metric family in both
    # rule copies (repo-root reference + chart-shipped)
    for rules in (REPO / "observability" / "alert-rules.yaml",
                  REPO / "helm" / "rules" / "alert-rules.yaml"):
        text = rules.read_text()
        assert "BrownoutSustained" in text
        assert "vllm:brownout_stage" in text


def test_canary_surface_is_inside_the_gates():
    """The correctness-canary surface (PR: router prober + golden store
    + drift alerts) is covered by the gates, not grandfathered:
    config-drift sees the --canary-* flags as declared router CLI flags
    (so the helm canary template block stays honest), metric-hygiene
    tracks the four vllm:canary_* families as defined in code AND
    documented, the chart's routerSpec.canary block is consumed by the
    router template with values-ci exercising the prober on CPU, and
    both alert-rule copies carry the identity-failure page + probe
    stall warning."""
    from tools.stackcheck.passes import config_drift, metric_hygiene

    ctx = core.Context(REPO)
    router_flags = config_drift._parser_flags(
        ctx, REPO / "production_stack_tpu" / "router" / "app.py")
    assert {"--canary", "--canary-interval", "--canary-golden-path",
            "--canary-timeout", "--canary-target"} <= router_flags

    # exposition adds _total to the counters; the gate pins base names
    canary = {"vllm:canary_probes", "vllm:canary_ttft_seconds",
              "vllm:canary_logit_error", "vllm:canary_identity_failures"}
    defined = metric_hygiene.code_metrics(ctx)
    assert canary <= defined
    documented = metric_hygiene.doc_refs(ctx)
    assert canary <= documented

    values = (REPO / "helm" / "values.yaml").read_text()
    assert "canary:" in values and "goldenPath:" in values
    values_ci = (REPO / "helm" / "values-ci.yaml").read_text()
    assert "canary:" in values_ci
    router_tmpl = (REPO / "helm" / "templates"
                   / "deployment-router.yaml").read_text()
    assert ("--canary" in router_tmpl
            and "--canary-golden-path" in router_tmpl
            and "routerSpec.canary" in router_tmpl)

    # the drift page + stall warning ride the canary families in both
    # rule copies (repo-root reference + chart-shipped)
    for rules in (REPO / "observability" / "alert-rules.yaml",
                  REPO / "helm" / "rules" / "alert-rules.yaml"):
        text = rules.read_text()
        assert "CanaryIdentityFailure" in text
        assert "CanaryProbeStall" in text
        assert "vllm:canary_identity_failures_total" in text
        assert "vllm:canary_probes_total" in text


def test_repo_has_no_active_findings():
    report = core.run_passes(
        REPO, baseline_path=REPO / core.BASELINE_DEFAULT)
    assert not report.active, "\n".join(f.render() for f in report.active)
    # every suppression in the repo proper carries a rationale (text after
    # the directive on the same line)
    for f in report.suppressed:
        text = (REPO / f.path).read_text().splitlines()
        directive = [ln for ln in text if "stackcheck: disable=" in ln]
        assert directive, f.path
