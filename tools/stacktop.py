"""stacktop — a terminal fleet view over the router's ``/debug/fleet``.

One row per engine (status, chip count, per-chip MFU/ICI utilization,
HBM, KV free, queue depth, QPS, TTFT, open incidents) plus the router's
SLO / scale / incident summary — the ``top``-alike for a serving fleet.
Pure stdlib so it runs from any operator box with nothing installed::

    python -m tools.stacktop --router http://localhost:8001
    python -m tools.stacktop --router http://localhost:8001 --watch 5

``render_table`` is a pure snapshot→string function so tests (and other
tools) can feed it a recorded /debug/fleet document.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request

# MFU/ICI are per-chip-honest utilizations (the engine's accountant
# scales its FLOP/HBM ceilings by CHIPS and counts per-chip collective
# bytes against the per-chip ICI link peak), so a TP=8 engine and a
# single-chip one compare directly in the same table.
COLUMNS = (
    ("ENGINE", 28), ("MODEL", 14), ("ROLE", 7), ("STATUS", 10), ("CHIPS", 5),
    ("MFU", 6), ("ICI", 6), ("HBM", 12), ("KVFREE", 7),
    ("HOSTHIT", 7), ("WAIT", 5), ("RUN", 5), ("QPS", 6), ("TTFT", 7),
    ("TENANT", 14), ("CANARY", 12), ("INCIDENTS", 14),
)

# --tenants mode: one row per tenant, aggregated across every engine's
# attribution block (chip-second conservation means SHARE sums to 100%)
TENANT_COLUMNS = (
    ("TENANT", 20), ("PREFILL", 10), ("DECODE", 10), ("CHIPSEC", 10),
    ("SHARE", 7), ("KVBLK", 7), ("REQS", 7), ("QUEUE", 8),
)

# --canary mode: one row per (model, probe) from the router's prober
# state — golden version, probe age, outcome, logit error
CANARY_COLUMNS = (
    ("MODEL", 16), ("PROBE", 14), ("PATH", 8), ("OUTCOME", 10),
    ("KIND", 12), ("LINF", 10), ("GOLDEN", 7), ("AGE", 7), ("ROUNDS", 7),
    ("FAILS", 6),
)


def _fmt_pct(x) -> str:
    return "-" if x is None else f"{x * 100:.1f}%"


def _fmt_num(x, spec: str = ".2f") -> str:
    if x is None:
        return "-"
    # engines scrape counts out of prometheus gauges, so "waiting: 0.0"
    # is the wire format even for integral quantities
    return format(int(x) if spec == "d" else x, spec)


def _fmt_hbm(used, total) -> str:
    if used is None or total is None or not total:
        return "-"
    gib = 1024 ** 3
    return f"{used / gib:.1f}/{total / gib:.1f}G"


def _fmt_host_hit(row: dict) -> str:
    """Warm-tier (host DRAM) KV hit ratio from the engine's kv_tier
    snapshot; '-' for engines without tiering or before the first query."""
    tiers = (row.get("kv_tier") or {}).get("tiers") or {}
    host = tiers.get("host") or {}
    queries = host.get("queries") or 0
    if not queries:
        return "-"
    return f"{host.get('hits', 0) / queries * 100:.1f}%"


def _fmt_top_tenant(row: dict) -> str:
    """Dominant tenant by chip-second share from the engine's attribution
    block; '-' for engines with metering off or before the first token."""
    block = row.get("tenants") or {}
    tenants = block.get("tenants") or {}
    total = sum((t or {}).get("chip_seconds", 0.0) for t in tenants.values())
    if not total:
        return "-"
    name, rec = max(tenants.items(),
                    key=lambda kv: (kv[1] or {}).get("chip_seconds", 0.0))
    return f"{name} {rec.get('chip_seconds', 0.0) / total * 100:.0f}%"


def _fmt_canary(row: dict) -> str:
    """Last canary verdict for this engine's model (worst across its
    models): outcome plus the observed L-infinity logit error; '-' for
    fleets without the canary plane or before the first probe."""
    c = row.get("canary") or {}
    outcome = c.get("outcome")
    if not outcome:
        return "-"
    linf = c.get("linf")
    if outcome in ("ok", "drift") and linf is not None and linf >= 0:
        return f"{outcome} {linf:.2g}"
    return outcome


def _clip(s: str, width: int) -> str:
    s = str(s)
    return s if len(s) <= width else s[: width - 1] + "…"


def engine_row_cells(row: dict) -> list:
    return [
        row.get("url", "-"),
        ",".join(row.get("models") or []) or "-",
        row.get("role") or row.get("label") or "-",
        row.get("status", "-"),
        _fmt_num(row.get("chips"), "d"),
        _fmt_pct(row.get("mfu")),
        _fmt_pct(row.get("ici")),
        _fmt_hbm(row.get("hbm_used_bytes"), row.get("hbm_total_bytes")),
        _fmt_pct(row.get("kv_free")),
        _fmt_host_hit(row),
        _fmt_num(row.get("waiting"), "d"),
        _fmt_num(row.get("running"), "d"),
        _fmt_num(row.get("qps")),
        _fmt_num(row.get("ttft"), ".3f"),
        _fmt_top_tenant(row),
        _fmt_canary(row),
        ",".join(row.get("incidents") or []) or "-",
    ]


def render_table(snapshot: dict) -> str:
    """Pure /debug/fleet document → multi-line table string."""
    lines = []
    header = "  ".join(name.ljust(width) for name, width in COLUMNS)
    lines.append(header)
    lines.append("-" * len(header))
    for row in snapshot.get("engines", []):
        cells = engine_row_cells(row)
        lines.append("  ".join(
            _clip(cell, width).ljust(width)
            for cell, (_, width) in zip(cells, COLUMNS)))
    if not snapshot.get("engines"):
        lines.append("(no engines discovered)")

    router = snapshot.get("router") or {}
    incidents = router.get("incidents") or {}
    open_count = incidents.get("open", 0)
    lines.append("")
    lines.append(f"incidents open: {open_count}")
    for inc in incidents.get("incidents", []):
        if inc.get("status") != "open":
            continue
        lines.append(
            f"  {inc['id']}  {inc['trigger']}  key={inc['key']}  "
            f"engines={','.join(inc.get('implicated') or []) or '-'}")
    slo = router.get("slo") or {}
    paging = [s for s in slo.get("series", []) if s.get("page")]
    if paging:
        lines.append("slo pages: " + ", ".join(
            f"{s['model']}/{s['slo']}" for s in paging))
    scale = router.get("scale") or {}
    models = scale.get("models") or {}
    if models:
        lines.append("scale: " + ", ".join(
            f"{name}→{rec.get('desired_replicas')}"
            for name, rec in sorted(models.items())))
    disagg = router.get("disagg") or {}
    if disagg:
        lines.append("disagg: " + ", ".join(
            f"{outcome}={count}"
            for outcome, count in sorted(disagg.items())))
    return "\n".join(lines)


def render_tenants(snapshot: dict) -> str:
    """Pure /debug/fleet document → per-tenant attribution table,
    aggregated across every engine's tenants block. SHARE is each
    tenant's fraction of all attributed chip-seconds — conservation
    means the column sums to ~100% whenever any engine metered."""
    agg: dict[str, dict] = {}
    for row in snapshot.get("engines", []):
        block = row.get("tenants") or {}
        for name, rec in (block.get("tenants") or {}).items():
            rec = rec or {}
            a = agg.setdefault(name, {
                "prefill_tokens": 0, "decode_tokens": 0,
                "chip_seconds": 0.0, "kv_blocks": 0, "requests": 0,
                "queue_seconds_sum": 0.0,
            })
            for key in a:
                a[key] += rec.get(key, 0)
    total_chip = sum(a["chip_seconds"] for a in agg.values())

    lines = []
    header = "  ".join(name.ljust(width) for name, width in TENANT_COLUMNS)
    lines.append(header)
    lines.append("-" * len(header))
    for name in sorted(agg, key=lambda n: -agg[n]["chip_seconds"]):
        a = agg[name]
        reqs = a["requests"]
        cells = [
            name,
            _fmt_num(a["prefill_tokens"], "d"),
            _fmt_num(a["decode_tokens"], "d"),
            _fmt_num(a["chip_seconds"], ".3f"),
            (f"{a['chip_seconds'] / total_chip * 100:.1f}%"
             if total_chip else "-"),
            _fmt_num(a["kv_blocks"], "d"),
            _fmt_num(reqs, "d"),
            (_fmt_num(a["queue_seconds_sum"] / reqs, ".4f")
             if reqs else "-"),
        ]
        lines.append("  ".join(
            _clip(cell, width).ljust(width)
            for cell, (_, width) in zip(cells, TENANT_COLUMNS)))
    if not agg:
        lines.append("(no tenant attribution — engines meter with "
                     "--no-tenant-metering unset)")

    router_block = (snapshot.get("router") or {}).get("tenants") or {}
    tenants = router_block.get("tenants") or {}
    if tenants:
        lines.append("")
        lines.append("router (5m window): " + ", ".join(
            f"{name}={rec.get('requests', 0)}req"
            + (f"/{rec['avg_ttft']:.3f}s ttft"
               if rec.get("avg_ttft", -1) >= 0 else "")
            for name, rec in sorted(tenants.items())))
    return "\n".join(lines)


def render_canary(snapshot: dict) -> str:
    """Pure /debug/fleet document → correctness-canary table: one row
    per (model, probe) from the router's prober state, plus the golden
    store's per-record versions."""
    block = (snapshot.get("router") or {}).get("canary") or {}
    lines = []
    if not block.get("enabled"):
        lines.append("(canary plane disabled — start the router with "
                     "--canary)")
        return "\n".join(lines)
    header = "  ".join(name.ljust(width) for name, width in CANARY_COLUMNS)
    lines.append(header)
    lines.append("-" * len(header))
    probes = block.get("probes") or []
    for st in probes:
        linf = st.get("linf")
        cells = [
            st.get("model", "-"),
            st.get("probe", "-"),
            st.get("role_path", "-"),
            st.get("outcome") or "(pending)",
            st.get("kind") or "-",
            ("-" if linf is None or linf < 0 else f"{linf:.3g}"),
            f"v{st.get('golden_version', 0)}",
            _fmt_num(st.get("age"), ".1f"),
            _fmt_num(st.get("rounds"), "d"),
            _fmt_num(st.get("failures"), "d"),
        ]
        lines.append("  ".join(
            _clip(cell, width).ljust(width)
            for cell, (_, width) in zip(cells, CANARY_COLUMNS)))
    if not probes:
        lines.append("(no probes yet — first round pending)")
    golden = block.get("golden") or {}
    records = golden.get("records") or []
    lines.append("")
    lines.append(
        f"golden store: {len(records)} record(s)"
        + (f" @ {golden.get('path')}" if golden.get("path") else
           " (empty — seed with tools/canaryctl.py record)"))
    age = block.get("last_round_age")
    if age is not None and age >= 0:
        lines.append(f"last round: {age:.1f}s ago "
                     f"(interval {block.get('interval')}s, "
                     f"rounds {block.get('rounds')})")
    return "\n".join(lines)


def fetch_fleet(router: str, timeout: float = 10.0) -> dict:
    url = router.rstrip("/") + "/debug/fleet"
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        "stacktop", description="terminal fleet view over /debug/fleet")
    p.add_argument("--router", default="http://localhost:8001",
                   help="router base URL")
    p.add_argument("--watch", type=float, default=0.0, metavar="SECONDS",
                   help="refresh every N seconds (0 = one shot)")
    p.add_argument("--json", action="store_true",
                   help="print the raw /debug/fleet document instead")
    p.add_argument("--tenants", action="store_true",
                   help="per-tenant attribution table (tokens, "
                        "chip-seconds, fairness share) instead of the "
                        "engine table")
    p.add_argument("--canary", action="store_true",
                   help="correctness-canary table (per-model golden "
                        "version, probe age, drift verdicts) instead "
                        "of the engine table")
    args = p.parse_args(argv)

    while True:
        try:
            snap = fetch_fleet(args.router)
        except Exception as e:
            print(f"stacktop: cannot reach {args.router}: {e}",
                  file=sys.stderr)
            if not args.watch:
                return 1
            time.sleep(args.watch)
            continue
        if args.json:
            out = json.dumps(snap, indent=2, default=str)
        else:
            stamp = time.strftime("%H:%M:%S", time.localtime(
                snap.get("ts", time.time())))
            table = (render_tenants(snap) if args.tenants
                     else render_canary(snap) if args.canary
                     else render_table(snap))
            out = f"stacktop @ {stamp}  ({args.router})\n" + table
        if args.watch:
            # clear + home, like watch(1), so the table repaints in place
            sys.stdout.write("\x1b[2J\x1b[H")
        print(out)
        if not args.watch:
            return 0
        time.sleep(args.watch)


if __name__ == "__main__":
    raise SystemExit(main())
