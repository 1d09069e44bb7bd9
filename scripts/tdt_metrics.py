#!/usr/bin/env python
"""Render triton_dist_tpu telemetry snapshots.

A process exposes its registry two ways: as a JSON file — explicitly via
``telemetry.dump(path)`` or automatically at exit with
``TDT_TELEMETRY_DUMP=/path/snap.json`` — or live over HTTP when
``TDT_HTTP_PORT`` is set (``runtime/introspect.py``). Every subcommand
takes either: a path, or an ``http://host:port`` base URL (the CLI fetches
``/snapshot`` from it).

Usage::

    python scripts/tdt_metrics.py show SRC          # human-readable summary
    python scripts/tdt_metrics.py show SRC --quantiles
                                                    # + full digest quantile
                                                    # table (p50..p999)
    python scripts/tdt_metrics.py prom SRC          # Prometheus exposition
                                                    # (digests render as
                                                    # summary-quantile lines)
    python scripts/tdt_metrics.py trace <id|last> SRC   # span tree of one
                                                        # request trace
    python scripts/tdt_metrics.py watch SRC [-n SECS] [-c COUNT]
                                                    # poll + render counter
                                                    # deltas between polls
    python scripts/tdt_metrics.py fleet URL [-n SECS] [-c COUNT]
                                                    # top-like fleet view off a
                                                    # ROUTER endpoint
                                                    # (/fleet/topology +
                                                    # /fleet/metrics)
    python scripts/tdt_metrics.py demo [out.json]   # tiny CPU serve -> live
                                                    # snapshot (smoke check)

See ``docs/observability.md`` for the metric naming convention and the full
set of env flags.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _load(src: str) -> dict:
    """Snapshot dict from a file path or an introspection endpoint base URL
    (``http://127.0.0.1:8080`` → fetches ``/snapshot``)."""
    if src.startswith(("http://", "https://")):
        import urllib.request

        url = src.rstrip("/")
        if not url.endswith("/snapshot"):
            url += "/snapshot"
        with urllib.request.urlopen(url, timeout=10) as r:
            return json.load(r)
    with open(src) as f:
        return json.load(f)


def _fmt_labels(labels: dict) -> str:
    if not labels:
        return ""
    return "{" + ",".join(f"{k}={v}" for k, v in labels.items()) + "}"


def cmd_show(path: str, quantiles: bool = False) -> int:
    snap = _load(path)
    print(f"telemetry snapshot: {path} (enabled={snap.get('enabled')})")
    counters = snap.get("counters", {})
    if counters:
        print("\ncounters:")
        for name, entries in counters.items():
            for e in entries:
                print(f"  {name}{_fmt_labels(e['labels'])} = {e['value']:g}")
    gauges = snap.get("gauges", {})
    if gauges:
        print("\ngauges:")
        for name, entries in gauges.items():
            for e in entries:
                print(f"  {name}{_fmt_labels(e['labels'])} = {e['value']:g}")
    hists = snap.get("histograms", {})
    if hists:
        print("\nhistograms:")
        for name, entries in hists.items():
            for e in entries:
                n = e["count"]
                mean = e["sum"] / n if n else 0.0
                # p50/p95 from the cumulative buckets (upper-bound estimate).
                quantiles = {}
                for bound, cum in e["buckets"]:
                    for q in (0.5, 0.95):
                        if q not in quantiles and n and cum >= q * n:
                            quantiles[q] = bound
                q50 = quantiles.get(0.5, "+Inf")
                q95 = quantiles.get(0.95, "+Inf")
                print(
                    f"  {name}{_fmt_labels(e['labels'])}: count={n} "
                    f"mean={mean:.6g}s p50<={q50} p95<={q95}"
                )
    digests = snap.get("digests", {})
    if digests:
        print("\ndigests (mergeable quantile sketches, "
              f"rel. error {_digest_alpha(digests):g}):")
        for name, entries in digests.items():
            for e in entries:
                qs = e.get("quantiles") or {}
                n = e["count"]
                mean = e["sum"] / n if n else 0.0
                if quantiles:
                    # Recompute any quantile from the serialized sketch —
                    # the full table, not just the pre-attached ones.
                    from triton_dist_tpu.runtime import telemetry

                    d = telemetry.Digest.from_dict(e)
                    row = " ".join(
                        f"p{q * 100:g}={d.quantile(q):.6g}"
                        for q in (0.5, 0.9, 0.95, 0.99, 0.999)
                        if d.quantile(q) is not None
                    )
                    mn, mx = e.get("min"), e.get("max")
                    print(
                        f"  {name}{_fmt_labels(e['labels'])}: count={n} "
                        f"mean={mean:.6g} "
                        f"min={'-' if mn is None else f'{mn:.6g}'} "
                        f"max={'-' if mx is None else f'{mx:.6g}'}\n    {row}"
                    )
                else:
                    p50, p99 = qs.get("p50"), qs.get("p99")
                    print(
                        f"  {name}{_fmt_labels(e['labels'])}: count={n} "
                        f"mean={mean:.6g} "
                        f"p50={'-' if p50 is None else f'{p50:.6g}'} "
                        f"p99={'-' if p99 is None else f'{p99:.6g}'}"
                    )
    evs = snap.get("events", [])
    if evs:
        print(f"\nevents ({len(evs)} in ring, newest last):")
        for e in evs[-20:]:
            kind = e.get("kind", "?")
            rest = {k: v for k, v in e.items() if k not in ("kind", "seq")}
            print(f"  [{e.get('seq', '?')}] {kind}: {rest}")
    traces = snap.get("kernel_traces", [])
    if traces:
        print(f"\nkernel traces: {len(traces)} rank-buffers collected")
        for t in traces:
            print(
                f"  {t['kernel']} rank={t['rank']}: "
                f"{len(t.get('events', []))} events, "
                f"{t.get('n_dropped', 0)} dropped"
            )
    tr = snap.get("traces", {})
    if tr.get("traces"):
        print(f"\nspan traces: {len(tr['traces'])} trace(s), "
              f"{tr.get('n_open', 0)} open span(s) — "
              f"`trace <id|last>` for the tree")
        for t in tr["traces"][-10:]:
            root = next((s for s in t["spans"] if s["parent_id"] is None), None)
            print(f"  trace {t['trace_id']}: "
                  f"{root['name'] if root else '?'}, {len(t['spans'])} span(s)")
    return 0


def _digest_alpha(digests: dict) -> float:
    for entries in digests.values():
        for e in entries:
            if "alpha" in e:
                return float(e["alpha"])
    return 0.0


def cmd_prom(path: str) -> int:
    from triton_dist_tpu.runtime import telemetry

    sys.stdout.write(telemetry.to_prometheus(_load(path)))
    return 0


def cmd_trace(which: str, src: str) -> int:
    """Render one trace's span tree (durations in ms, parent-indented)."""
    snap = _load(src)
    traces = snap.get("traces", {}).get("traces", [])
    if not traces:
        print(f"no span traces in {src}", file=sys.stderr)
        return 1
    if which == "last":
        entry = traces[-1]
    else:
        try:
            tid = int(which)
        except ValueError:
            print(f"trace id must be an integer or 'last', got {which!r}",
                  file=sys.stderr)
            return 2
        match = [t for t in traces if t["trace_id"] == tid]
        if not match:
            known = [t["trace_id"] for t in traces]
            print(f"unknown trace {tid} (known: {known})", file=sys.stderr)
            return 1
        entry = match[0]
    spans = entry["spans"]
    by_parent: dict[int | None, list[dict]] = {}
    ids = {s["span_id"] for s in spans}
    for s in spans:
        # A span whose parent fell off the bounded ring renders as a root.
        parent = s["parent_id"] if s["parent_id"] in ids else None
        by_parent.setdefault(parent, []).append(s)
    t0 = min(s["start_s"] for s in spans)

    def render(parent: int | None, depth: int) -> None:
        for s in sorted(by_parent.get(parent, []), key=lambda x: x["start_s"]):
            end = s["end_s"]
            dur = "open" if end is None else f"{(end - s['start_s']) * 1e3:.2f}ms"
            attrs = {k: v for k, v in s["attrs"].items()}
            at = f" {attrs}" if attrs else ""
            print(
                f"  {'  ' * depth}{s['name']} [+{(s['start_s'] - t0) * 1e3:.2f}ms "
                f"{dur}]{at}"
            )
            render(s["span_id"], depth + 1)

    print(f"trace {entry['trace_id']}: {len(spans)} span(s)")
    render(None, 0)
    return 0


def cmd_watch(src: str, interval_s: float, count: int) -> int:
    """Poll ``src`` and print counter/gauge deltas between polls — the
    poor-operator's rate() for a live endpoint or a re-dumped file."""

    def flat(snap: dict, kind: str) -> dict[str, float]:
        out = {}
        for name, entries in snap.get(kind, {}).items():
            for e in entries:
                out[name + _fmt_labels(e["labels"])] = e["value"]
        return out

    prev = None
    for i in range(count):
        try:
            snap = _load(src)
        except Exception as e:  # endpoint not up yet / file mid-write
            print(f"[watch] poll failed: {type(e).__name__}: {e}")
            time.sleep(interval_s)
            continue
        counters = flat(snap, "counters")
        gauges = flat(snap, "gauges")
        tr = snap.get("traces", {})
        stamp = time.strftime("%H:%M:%S")
        if prev is None:
            print(f"[{stamp}] baseline: {len(counters)} counters, "
                  f"{len(gauges)} gauges, {tr.get('n_open', 0)} open span(s)")
        else:
            deltas = {
                k: v - prev.get(k, 0.0)
                for k, v in counters.items()
                if v != prev.get(k, 0.0)
            }
            if deltas:
                print(f"[{stamp}] deltas over {interval_s:g}s:")
                for k, d in sorted(deltas.items()):
                    print(f"  {k} +{d:g}")
            else:
                print(f"[{stamp}] no counter movement")
            for k, v in sorted(gauges.items()):
                print(f"  {k} = {v:g}")
        prev = counters
        if i + 1 < count:
            time.sleep(interval_s)
    return 0


def cmd_fleet(base: str, interval_s: float, count: int) -> int:
    """Top-like fleet view off a ROUTER introspection endpoint: one row per
    replica from ``/fleet/topology`` plus the fleet-summed counters from
    ``/fleet/metrics?format=json`` (count=1 for a one-shot snapshot)."""
    import urllib.request

    base = base.rstrip("/")
    if not base.startswith(("http://", "https://")):
        print(f"fleet needs a router endpoint URL, got {base!r}",
              file=sys.stderr)
        return 2

    def fetch(path: str) -> dict:
        with urllib.request.urlopen(base + path, timeout=10) as r:
            return json.load(r)

    prev: dict[str, float] = {}
    for i in range(count):
        try:
            topo = fetch("/fleet/topology")
            metrics = fetch("/fleet/metrics?format=json")
        except Exception as e:  # router endpoint down / replica mid-rebuild
            print(f"[fleet] poll failed: {type(e).__name__}: {e}")
            time.sleep(interval_s)
            continue
        stamp = time.strftime("%H:%M:%S")
        print(f"[{stamp}] fleet: {len(topo['replicas'])} replica(s), "
              f"pending={topo['pending']} "
              f"done={topo['done']}/{topo['requests']} "
              f"affinity={topo['affinity']}")
        if topo.get("disagg"):
            pools = ", ".join(
                f"{role}={idxs}" for role, idxs in
                sorted(topo.get("pools", {}).items())
            )
            hoffs = topo.get("handoffs", {})
            print(f"  disagg pools: {pools}  handoffs: "
                  f"pending={hoffs.get('pending', 0)} "
                  f"ok={hoffs.get('ok', 0)} "
                  f"fallback={hoffs.get('fallback', 0)}")
        hdr = (f"  {'idx':>3} {'gen':>3} {'state':<8} {'role':<8} "
               f"{'port':>6} "
               f"{'infl':>4} {'place':>6} {'hit%':>6} {'est_wait':>9} "
               f"{'backlog':>8} {'queue':>5}")
        print(hdr)
        for rep in topo["replicas"]:
            state = ("drain" if rep["draining"] else
                     "up" if rep["alive"] else "DOWN")
            load = rep.get("load") or {}
            est = load.get("est_wait_s")
            print(f"  {rep['idx']:>3} {rep['gen']:>3} {state:<8} "
                  f"{rep.get('role', 'unified'):<8} "
                  f"{rep['port'] or '-':>6} {rep['inflight']:>4} "
                  f"{rep['placements']:>6} {rep['hit_rate'] * 100:>5.1f}% "
                  f"{'-' if est is None else f'{est:.3f}s':>9} "
                  f"{load.get('backlog_tokens', '-'):>8} "
                  f"{load.get('queue_depth', '-'):>5}")
        if topo.get("postmortems"):
            print(f"  postmortems: replicas {topo['postmortems']} "
                  f"(see /fleet/postmortem/<idx>)")
        # Fleet-summed counters (the replica-label-free series) with deltas.
        sums = {}
        for name, entries in metrics.get("counters", {}).items():
            for e in entries:
                if "replica" not in e["labels"]:
                    sums[name + _fmt_labels(e["labels"])] = e["value"]
        shown = sorted(k for k in sums if k.startswith("tdt_serving_")
                       or k.startswith("tdt_fleet_")
                       or k.startswith("tdt_disagg_"))
        if shown:
            print("  fleet counters (summed across replicas):")
            for k in shown:
                delta = sums[k] - prev.get(k, 0.0)
                d = f" (+{delta:g})" if prev and delta else ""
                print(f"    {k} = {sums[k]:g}{d}")
        prev = sums
        if i + 1 < count:
            time.sleep(interval_s)
    return 0


def cmd_demo(out: str | None) -> int:
    """Serve a few tokens from the tiny test model on the 8-device CPU mesh
    and show the live registry — the zero-to-snapshot smoke path."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from triton_dist_tpu.runtime import telemetry
    from triton_dist_tpu.runtime.platform import cpu_mesh, use_cpu_devices
    from triton_dist_tpu.runtime.mesh import initialize_distributed

    use_cpu_devices(8)
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.models import PRESETS, DenseLLM, Engine

    m = cpu_mesh((4,), ("tp",))
    ctx = initialize_distributed(
        devices=list(m.devices.flat), axis_names=("tp",), set_default=False
    )
    model = DenseLLM(PRESETS["test-dense"], ctx, key=jax.random.PRNGKey(0))
    eng = Engine(model, backend="xla", max_len=32)
    ids = jnp.zeros((1, 8), jnp.int32)
    jax.block_until_ready(eng.serve(ids, gen_len=4))

    if out:
        print(f"wrote {telemetry.dump(out)}")
        return cmd_show(out)
    sys.stdout.write(telemetry.to_prometheus())
    return 0


def main(argv: list[str]) -> int:
    if len(argv) >= 2 and argv[0] == "show":
        quantiles = "--quantiles" in argv[1:]
        rest = [a for a in argv[1:] if a != "--quantiles"]
        if len(rest) != 1:
            print("usage: show SRC [--quantiles]", file=sys.stderr)
            return 2
        return cmd_show(rest[0], quantiles=quantiles)
    if len(argv) >= 2 and argv[0] == "prom":
        return cmd_prom(argv[1])
    if len(argv) >= 3 and argv[0] == "trace":
        return cmd_trace(argv[1], argv[2])
    if len(argv) >= 2 and argv[0] == "watch":
        interval, count = 2.0, 10
        rest = argv[2:]
        i = 0
        while i < len(rest):
            if rest[i] == "-n" and i + 1 < len(rest):
                interval = float(rest[i + 1]); i += 2
            elif rest[i] == "-c" and i + 1 < len(rest):
                count = int(rest[i + 1]); i += 2
            else:
                print(f"unknown watch arg {rest[i]!r}", file=sys.stderr)
                return 2
        return cmd_watch(argv[1], interval, count)
    if len(argv) >= 2 and argv[0] == "fleet":
        interval, count = 2.0, 1
        rest = argv[2:]
        i = 0
        while i < len(rest):
            if rest[i] == "-n" and i + 1 < len(rest):
                interval = float(rest[i + 1]); i += 2
            elif rest[i] == "-c" and i + 1 < len(rest):
                count = int(rest[i + 1]); i += 2
            else:
                print(f"unknown fleet arg {rest[i]!r}", file=sys.stderr)
                return 2
        return cmd_fleet(argv[1], interval, count)
    if argv and argv[0] == "demo":
        return cmd_demo(argv[1] if len(argv) > 1 else None)
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
