"""Summarize the host riders' banked artifacts, one table per rider.

Summarizes the batched-ingest rider artifacts
(``bench-artifacts/ingest-<stamp>.json``, written by bench.py's
measure_batched_ingest): host sealing, client build, and REST ingest
rates plus the measured telemetry overhead, one row per run — the
host-plane trend line.

Also tabulates the clerking-pipeline rider artifacts
(``bench-artifacts/clerking-<stamp>.json``, written by bench.py's
measure_clerking_pipeline): one row per delivery config (monolithic
baseline + each paged chunk size) with throughput, the ratio against the
monolithic baseline from the SAME run, peak clerk RSS, and the measured
download-overlap efficiency.

Also tabulates the reveal-pipeline rider artifacts
(``bench-artifacts/reveal-<stamp>.json``, written by bench.py's
measure_reveal_pipeline) in the same shape: monolithic vs chunked reveal
per cohort size, with peak recipient RSS and overlap efficiency — the
evidence that reveal memory stays flat in N.

Also tabulates the committee-scaling rider artifacts
(``bench-artifacts/committee-<stamp>.json``, written by bench.py's
measure_committee_scaling): one row per crypto plane (clerking / reveal /
ingest) per SDA_WORKERS count, plus the sqlite read-pool thread probe,
with a scaling-efficiency column (speedup over the serial run divided by
the worker count; 1.0 = perfect scaling) and the host cpu_count the run
measured on.

Also tabulates the wire-transport rider artifacts
(``bench-artifacts/wire-<stamp>.json``, written by bench.py's
measure_wire_transport): one row per run with the JSON-leg and binary-leg
ingest rates measured over the same live keep-alive server, the
binary-vs-json ratio, the ratio against the recorded ~11K/s pre-binary
JSON baseline (the wire plane's acceptance bar), the clerking-fetch and
reveal ratios, and whether server RSS stayed flat across the legs.

Also tabulates the tier-fanout rider artifacts
(``bench-artifacts/tier-<stamp>.json``, written by bench.py's
measure_tier_fanout): one row per fan-out config (flat baseline + each
2-tier fan-out m) with the largest clerk job in columns, its ratio
against the flat N, mean stage seconds per clerk job, clerked inputs
per clerk-second, and the honestly-reported single-core round wall —
the evidence that hierarchical committees shrink the per-clerk bound
even where one CPU serializes every committee. Artifacts that carry the
promotion A/B leg get a second table: per-node driver promotion latency
under the reveal round-trip vs share-promotion, side by side.

Also tabulates the sustained-soak rider artifacts (``soak-<stamp>.json``
and the fault-axis variants ``replica-soak-*`` / ``grow-soak-*``, written
by scripts/load_soak.py) and the flagship campaign artifacts
(``flagship-<stamp>.json``, written by scripts/flagship.py): one row per
campaign with the process/shard/replica topology, the certified-max-
cohort headline and its implied scale factor against the simulated
population, rungs certified vs attempted, the peak certified
phones-per-second, and the merged cross-process telemetry coverage.
Flagship artifacts carrying the within-run arrivals A/B leg get a second
table: the serial vs pipelined ``rung.arrivals`` walls at the same
cohort, side by side with the gated speedup ratio.

Also tabulates the sketch-accuracy rider artifacts
(``bench-artifacts/sketch-<stamp>.json``, written by bench.py's
measure_sketch_accuracy): the accuracy-vs-dimension table — one row per
sketch family per wire dimension with the observed error, the analytic
bound, the headroom ratio (bound / observed error, >= 1 inside bound),
the end-to-end items/s, and whether the secure sum stayed byte-exact.

Also rolls the churn harness's banked cells (``scenario-<name>-*.json``,
written by scripts/scenarios.py) into the survivability matrix: scenario
rows x (store, transport) columns, latest artifact per cell, OK / FAIL /
``--`` for never-run — plus any retry-layer overhead A/B records
(``overhead-ab-*.json``).

Usage: python scripts/sweep_report.py [artifact_dir]
"""

from __future__ import annotations

import json
import pathlib
import sys


#: rate/overhead columns lifted from each ingest artifact (absent keys —
#: older artifacts — render as "-")
_INGEST_COLS = (
    "seal_batch_per_s",
    "build_per_s",
    "participate_many_per_s",
    "rest_sqlite_batch_per_s",
    "rest_mem_batch_per_s",
    "telemetry_overhead_pct",
)


def load_ingest(artdir: pathlib.Path):
    rows = []
    for f in sorted(artdir.glob("ingest-*.json")):
        try:
            d = json.loads(f.read_text())
        except (OSError, ValueError):
            continue
        if not isinstance(d, dict) or all(d.get(k) is None for k in _INGEST_COLS):
            continue  # no rate-bearing fields: nothing to tabulate
        rows.append({"artifact": f.name, **{k: d.get(k) for k in _INGEST_COLS}})
    return rows


def print_ingest(rows) -> None:
    print("\nbatched-ingest riders (ingest-*.json):")
    print(
        f"{'seal/s':>8} {'build/s':>8} {'many/s':>8} {'sqlite/s':>9} "
        f"{'mem/s':>8} {'tel_ov%':>8}  artifact"
    )
    for r in rows:
        cells = [
            (r["seal_batch_per_s"], 8),
            (r["build_per_s"], 8),
            (r["participate_many_per_s"], 8),
            (r["rest_sqlite_batch_per_s"], 9),
            (r["rest_mem_batch_per_s"], 8),
            (r["telemetry_overhead_pct"], 8),
        ]
        row = " ".join(
            f"{v if v is not None else '-':>{w}}" for v, w in cells
        )
        print(f"{row}  {r['artifact']}")


def load_clerking(artdir: pathlib.Path):
    """One row per delivery config per clerking-*.json artifact."""
    rows = []
    for f in sorted(artdir.glob("clerking-*.json")):
        try:
            d = json.loads(f.read_text())
        except (OSError, ValueError):
            continue
        configs = d.get("configs") if isinstance(d, dict) else None
        if not isinstance(configs, dict):
            continue
        n = (d.get("config") or {}).get("n_participants")
        for tag, cfg in sorted(configs.items()):
            if not isinstance(cfg, dict) or cfg.get("encryptions_per_s") is None:
                continue
            rows.append(
                {
                    "artifact": f.name,
                    "tag": tag,
                    "n": n,
                    "chunk": cfg.get("chunk_size"),
                    "encs_per_s": cfg.get("encryptions_per_s"),
                    "vs_mono": cfg.get("vs_monolithic"),
                    "rss_mib": cfg.get("peak_rss_mib"),
                    "overlap": cfg.get("overlap_efficiency"),
                }
            )
    return rows


def print_clerking(rows) -> None:
    print("\nclerking-pipeline riders (clerking-*.json):")
    print(
        f"{'config':>14} {'n':>7} {'chunk':>6} {'encs/s':>9} {'vs_mono':>8} "
        f"{'rss_mib':>8} {'overlap':>8}  artifact"
    )
    for r in rows:
        overlap = f"{r['overlap']:.2f}" if r["overlap"] is not None else "-"
        print(
            f"{r['tag']:>14} {r['n'] if r['n'] is not None else '-':>7} "
            f"{r['chunk'] if r['chunk'] is not None else '-':>6} "
            f"{r['encs_per_s']:>9} "
            f"{r['vs_mono'] if r['vs_mono'] is not None else '-':>8} "
            f"{r['rss_mib'] if r['rss_mib'] is not None else '-':>8} "
            f"{overlap:>8}  {r['artifact']}"
        )


def load_reveal(artdir: pathlib.Path):
    """One row per delivery config per reveal-*.json artifact."""
    rows = []
    for f in sorted(artdir.glob("reveal-*.json")):
        try:
            d = json.loads(f.read_text())
        except (OSError, ValueError):
            continue
        configs = d.get("configs") if isinstance(d, dict) else None
        if not isinstance(configs, dict):
            continue
        for tag, cfg in sorted(configs.items()):
            if not isinstance(cfg, dict) or cfg.get("encryptions_per_s") is None:
                continue
            rows.append(
                {
                    "artifact": f.name,
                    "tag": tag,
                    "n": cfg.get("n_participants"),
                    "chunk": cfg.get("chunk_size"),
                    "encs_per_s": cfg.get("encryptions_per_s"),
                    "vs_mono": cfg.get("vs_monolithic"),
                    "rss_mib": cfg.get("peak_rss_mib"),
                    "overlap": cfg.get("overlap_efficiency"),
                }
            )
    return rows


def print_reveal(rows) -> None:
    print("\nreveal-pipeline riders (reveal-*.json):")
    print(
        f"{'config':>16} {'n':>7} {'chunk':>6} {'encs/s':>9} {'vs_mono':>8} "
        f"{'rss_mib':>8} {'overlap':>8}  artifact"
    )
    for r in rows:
        overlap = f"{r['overlap']:.2f}" if r["overlap"] is not None else "-"
        print(
            f"{r['tag']:>16} {r['n'] if r['n'] is not None else '-':>7} "
            f"{r['chunk'] if r['chunk'] is not None else '-':>6} "
            f"{r['encs_per_s']:>9} "
            f"{r['vs_mono'] if r['vs_mono'] is not None else '-':>8} "
            f"{r['rss_mib'] if r['rss_mib'] is not None else '-':>8} "
            f"{overlap:>8}  {r['artifact']}"
        )


def load_committee(artdir: pathlib.Path):
    """One row per plane x worker count (plus the read-pool thread probe)
    per committee-*.json artifact, with scaling efficiency = speedup over
    the serial run divided by the worker count (1.0 = perfect scaling)."""
    rows = []
    for f in sorted(artdir.glob("committee-*.json")):
        try:
            d = json.loads(f.read_text())
        except (OSError, ValueError):
            continue
        if not isinstance(d, dict):
            continue
        cpu = d.get("cpu_count")
        planes = d.get("planes") if isinstance(d.get("planes"), dict) else {}
        for plane, configs in sorted(planes.items()):
            if not isinstance(configs, dict):
                continue
            for _, cfg in sorted(configs.items()):
                if not isinstance(cfg, dict) or cfg.get("per_s") is None:
                    continue
                w, vs = cfg.get("workers"), cfg.get("vs_w1")
                rows.append(
                    {
                        "artifact": f.name,
                        "plane": plane,
                        "workers": w,
                        "per_s": cfg.get("per_s"),
                        "vs_w1": vs,
                        "efficiency": (
                            round(vs / w, 2) if vs is not None and w else None
                        ),
                        "rss_mib": cfg.get("peak_rss_mib"),
                        "identical": cfg.get("identical_to_serial"),
                        "cpu": cpu,
                    }
                )
        pool = d.get("read_pool") if isinstance(d.get("read_pool"), dict) else {}
        for _, cfg in sorted(pool.items()):
            if not isinstance(cfg, dict) or cfg.get("reads_per_s") is None:
                continue
            t, vs = cfg.get("threads"), cfg.get("vs_t1")
            rows.append(
                {
                    "artifact": f.name,
                    "plane": "read_pool",
                    "workers": t,
                    "per_s": cfg.get("reads_per_s"),
                    "vs_w1": vs,
                    "efficiency": (
                        round(vs / t, 2) if vs is not None and t else None
                    ),
                    "rss_mib": None,
                    # byte-identity is asserted on the crypto planes; the
                    # read probe verifies row counts instead
                    "identical": None,
                    "cpu": cpu,
                }
            )
    return rows


def print_committee(rows) -> None:
    print("\ncommittee-scaling riders (committee-*.json):")
    print(
        f"{'plane':>10} {'workers':>7} {'per_s':>9} {'vs_w1':>6} "
        f"{'scal_eff':>8} {'rss_mib':>8} {'ident':>5} {'cpus':>4}  artifact"
    )
    for r in rows:
        ident = "-" if r["identical"] is None else ("yes" if r["identical"] else "NO")
        print(
            f"{r['plane']:>10} {r['workers'] if r['workers'] is not None else '-':>7} "
            f"{r['per_s']:>9} "
            f"{r['vs_w1'] if r['vs_w1'] is not None else '-':>6} "
            f"{r['efficiency'] if r['efficiency'] is not None else '-':>8} "
            f"{r['rss_mib'] if r['rss_mib'] is not None else '-':>8} "
            f"{ident:>5} {r['cpu'] if r['cpu'] is not None else '-':>4}  "
            f"{r['artifact']}"
        )


def load_wire(artdir: pathlib.Path):
    """One row per wire-*.json artifact: both legs' ingest rates plus the
    ratio columns (vs the same-run JSON leg and vs the recorded pre-binary
    baseline)."""
    rows = []
    for f in sorted(artdir.glob("wire-*.json")):
        try:
            d = json.loads(f.read_text())
        except (OSError, ValueError):
            continue
        if not isinstance(d, dict):
            continue
        json_leg = d.get("json") if isinstance(d.get("json"), dict) else {}
        binary_leg = d.get("binary") if isinstance(d.get("binary"), dict) else {}
        if binary_leg.get("ingest_per_s") is None:
            continue  # no rate: nothing to tabulate
        rows.append(
            {
                "artifact": f.name,
                "n": d.get("n_participants"),
                "store": d.get("store"),
                "json_ingest_per_s": json_leg.get("ingest_per_s"),
                "binary_ingest_per_s": binary_leg.get("ingest_per_s"),
                "vs_json": d.get("ingest_binary_vs_json"),
                "vs_baseline": d.get("ingest_binary_vs_baseline"),
                "fetch_ratio": d.get("clerking_fetch_binary_vs_json"),
                "reveal_ratio": d.get("reveal_binary_vs_json"),
                "rss_flat": d.get("rss_flat"),
            }
        )
    return rows


def print_wire(rows) -> None:
    print("\nwire-transport riders (wire-*.json):")
    print(
        f"{'n':>7} {'store':>6} {'json/s':>8} {'binary/s':>9} {'vs_json':>8} "
        f"{'vs_base':>8} {'fetch_x':>8} {'reveal_x':>8} {'rss':>5}  artifact"
    )
    for r in rows:
        rss = "-" if r["rss_flat"] is None else ("flat" if r["rss_flat"] else "GREW")
        print(
            f"{r['n'] if r['n'] is not None else '-':>7} "
            f"{r['store'] if r['store'] is not None else '-':>6} "
            f"{r['json_ingest_per_s'] if r['json_ingest_per_s'] is not None else '-':>8} "
            f"{r['binary_ingest_per_s']:>9} "
            f"{r['vs_json'] if r['vs_json'] is not None else '-':>8} "
            f"{r['vs_baseline'] if r['vs_baseline'] is not None else '-':>8} "
            f"{r['fetch_ratio'] if r['fetch_ratio'] is not None else '-':>8} "
            f"{r['reveal_ratio'] if r['reveal_ratio'] is not None else '-':>8} "
            f"{rss:>5}  {r['artifact']}"
        )


def load_tier(artdir: pathlib.Path):
    """One row per fan-out config per tier-*.json artifact (flat baseline
    first, then each 2-tier fan-out), with the per-clerk-bound columns and
    the honestly-reported single-core wall ratio."""
    rows = []
    for f in sorted(artdir.glob("tier-*.json")):
        try:
            d = json.loads(f.read_text())
        except (OSError, ValueError):
            continue
        configs = d.get("configs") if isinstance(d, dict) else None
        if not isinstance(configs, dict):
            continue
        n = (d.get("config") or {}).get("n_participants")
        # flat first, then fan-outs ascending — sorted() would interleave
        for tag in ["flat"] + sorted(
            (t for t in configs if t != "flat"),
            key=lambda t: configs[t].get("fanout") or 0,
        ):
            cfg = configs.get(tag)
            if not isinstance(cfg, dict) or cfg.get("max_job_participations") is None:
                continue
            rows.append(
                {
                    "artifact": f.name,
                    "tag": tag,
                    "n": n,
                    "nodes": cfg.get("nodes"),
                    "max_job": cfg.get("max_job_participations"),
                    "vs_flat": cfg.get("vs_flat_max_job"),
                    "per_job_s": cfg.get("per_job_stage_s"),
                    "inputs_per_clerk_s": cfg.get("inputs_per_clerk_s"),
                    "wall_s": cfg.get("wall_s"),
                    "exact": cfg.get("exact"),
                }
            )
    return rows


def print_tier(rows) -> None:
    print("\ntier-fanout riders (tier-*.json):")
    print(
        f"{'config':>8} {'n':>6} {'nodes':>5} {'max_job':>8} {'vs_flat':>8} "
        f"{'job_s':>8} {'in/clk_s':>9} {'wall_s':>7} {'exact':>5}  artifact"
    )
    for r in rows:
        per_job = f"{r['per_job_s']:.5f}" if r["per_job_s"] is not None else "-"
        exact = "-" if r["exact"] is None else ("yes" if r["exact"] else "NO")
        print(
            f"{r['tag']:>8} {r['n'] if r['n'] is not None else '-':>6} "
            f"{r['nodes'] if r['nodes'] is not None else '-':>5} "
            f"{r['max_job']:>8} "
            f"{r['vs_flat'] if r['vs_flat'] is not None else '-':>8} "
            f"{per_job:>8} "
            f"{r['inputs_per_clerk_s'] if r['inputs_per_clerk_s'] is not None else '-':>9} "
            f"{r['wall_s'] if r['wall_s'] is not None else '-':>7} "
            f"{exact:>5}  {r['artifact']}"
        )


def load_promotion_ab(artdir: pathlib.Path):
    """One row per promotion path per tier-*.json artifact carrying the
    reveal-vs-share-promotion A/B leg (bench.py measure_tier_fanout):
    per-node driver promotion latency, its inverse rate, the clerk-side
    re-share cost reported alongside, and the reshare-vs-reveal ratio."""
    rows = []
    for f in sorted(artdir.glob("tier-*.json")):
        try:
            d = json.loads(f.read_text())
        except (OSError, ValueError):
            continue
        ab = d.get("promotion_ab") if isinstance(d, dict) else None
        if not isinstance(ab, dict):
            continue
        for path in ("reveal", "reshare"):
            leg = ab.get(path)
            if not isinstance(leg, dict):
                continue
            rows.append(
                {
                    "artifact": f.name,
                    "path": path,
                    "nodes": leg.get("promoted_nodes"),
                    "per_node_s": leg.get("per_node_promotion_s"),
                    "nodes_per_s": leg.get("promote_nodes_per_s"),
                    "clerk_reshare_s": leg.get("clerk_reshare_s"),
                    "wall_s": leg.get("wall_s"),
                    "vs_reveal": leg.get("vs_reveal_per_node"),
                    "exact": leg.get("exact"),
                }
            )
    return rows


def print_promotion_ab(rows) -> None:
    print("\ntier promotion A/B (reveal vs share-promotion, tier-*.json):")
    print(
        f"{'path':>8} {'nodes':>5} {'node_s':>9} {'nodes/s':>8} "
        f"{'clk_rshr_s':>10} {'wall_s':>7} {'vs_reveal':>9} {'exact':>5}  artifact"
    )
    for r in rows:
        per_node = f"{r['per_node_s']:.5f}" if r["per_node_s"] is not None else "-"
        exact = "-" if r["exact"] is None else ("yes" if r["exact"] else "NO")
        print(
            f"{r['path']:>8} "
            f"{r['nodes'] if r['nodes'] is not None else '-':>5} "
            f"{per_node:>9} "
            f"{r['nodes_per_s'] if r['nodes_per_s'] is not None else '-':>8} "
            f"{r['clerk_reshare_s'] if r['clerk_reshare_s'] is not None else '-':>10} "
            f"{r['wall_s'] if r['wall_s'] is not None else '-':>7} "
            f"{r['vs_reveal'] if r['vs_reveal'] is not None else '-':>9} "
            f"{exact:>5}  {r['artifact']}"
        )


def load_soak(artdir: pathlib.Path):
    """One row per soak-family artifact (soak-* / replica-soak-* /
    grow-soak-*, scripts/load_soak.py): rounds and
    exactness, sample count, mean/max total request rate, the worst
    windowed p99 over the hottest route, the RSS trajectory, and the
    sampler overhead A/B."""
    rows = []
    # the fault axes bank their own families (replica-soak-*, grow-soak-*)
    # so bench_compare stays apples-to-apples, but the report shows them
    # side by side — the artifact name carries the family
    names = sorted(
        f for pat in ("soak-*.json", "replica-soak-*.json", "grow-soak-*.json")
        for f in artdir.glob(pat)
    )
    for f in names:
        try:
            d = json.loads(f.read_text())
        except (OSError, ValueError):
            continue
        if not isinstance(d, dict) or d.get("kind") != "soak":
            continue
        summary = d.get("summary") if isinstance(d.get("summary"), dict) else {}
        p99s = summary.get("p99_s_by_route") or {}
        worst = None
        if p99s:
            worst_route = max(p99s, key=lambda r: p99s[r].get("max", 0))
            worst = (worst_route, p99s[worst_route].get("max"))
        rss = summary.get("rss_mib") or {}
        rows.append(
            {
                "artifact": f.name,
                "duration_s": (d.get("config") or {}).get("duration_s"),
                "rate": (d.get("config") or {}).get("rate"),
                "rounds": d.get("total_rounds"),
                "exact": d.get("exact_rounds"),
                "samples": len(d.get("samples") or []),
                "rps_mean": summary.get("rps_mean"),
                "rps_max": summary.get("rps_max"),
                "worst_p99": worst,
                "rss_start": rss.get("start"),
                "rss_peak": rss.get("peak"),
                "overhead_pct": d.get("sampler_overhead_pct"),
                "faults": (d.get("config") or {}).get("faults"),
            }
        )
    return rows


def print_soak(rows) -> None:
    print("\nsustained-soak riders (soak-*/replica-soak-*/grow-soak-*.json):")
    print(
        f"{'dur_s':>6} {'rate':>6} {'rounds':>6} {'exact':>6} {'smpls':>5} "
        f"{'rps_mean':>8} {'rps_max':>8} {'worst_p99':>24} "
        f"{'rss_mib':>13} {'smplr%':>7}  artifact"
    )
    for r in rows:
        exact = (
            "-" if r["exact"] is None
            else (f"{r['exact']}/{r['rounds']}" if r["exact"] != r["rounds"]
                  else "all")
        )
        worst = (
            f"{r['worst_p99'][1]:.4f}s {r['worst_p99'][0][-16:]}"
            if r["worst_p99"] and r["worst_p99"][1] is not None else "-"
        )
        rss = (
            f"{r['rss_start']}->{r['rss_peak']}"
            if r["rss_start"] is not None and r["rss_peak"] is not None else "-"
        )
        ov = f"{r['overhead_pct']:+.2f}" if r["overhead_pct"] is not None else "-"
        tag = " +faults" if r["faults"] else ""
        print(
            f"{r['duration_s'] if r['duration_s'] is not None else '-':>6} "
            f"{r['rate'] if r['rate'] is not None else '-':>6} "
            f"{r['rounds'] if r['rounds'] is not None else '-':>6} "
            f"{exact:>6} {r['samples']:>5} "
            f"{r['rps_mean'] if r['rps_mean'] is not None else '-':>8} "
            f"{r['rps_max'] if r['rps_max'] is not None else '-':>8} "
            f"{worst:>24} {rss:>13} {ov:>7}  {r['artifact']}{tag}"
        )


def load_flagship(artdir: pathlib.Path):
    """One row per flagship-*.json campaign (scripts/flagship.py): the
    composed-topology headline — certified max cohort, implied scale
    factor against the simulated population, rung ladder shape, peak
    certified phones/s, and the merged cross-process telemetry span."""
    rows = []
    for f in sorted(artdir.glob("flagship-*.json")):
        try:
            d = json.loads(f.read_text())
        except (OSError, ValueError):
            continue
        if not isinstance(d, dict) or d.get("kind") != "flagship":
            continue
        topo = d.get("topology") if isinstance(d.get("topology"), dict) else {}
        ladder = d.get("ladder") if isinstance(d.get("ladder"), list) else []
        certified = [r for r in ladder
                     if isinstance(r, dict) and r.get("certified")]
        rates = [
            r["cohort"] / r["round_s"] for r in certified
            if isinstance(r.get("cohort"), (int, float))
            and isinstance(r.get("round_s"), (int, float)) and r["round_s"] > 0
        ]
        merged = d.get("merged_samples") or []
        procs = [s.get("procs", 0) for s in merged if isinstance(s, dict)]
        rows.append(
            {
                "artifact": f.name,
                "frontends": topo.get("frontend_processes"),
                "shards": topo.get("shards"),
                "replicas": topo.get("replicas"),
                "tiers": topo.get("tiers"),
                "certified_max": d.get("certified_max_cohort"),
                "scale_factor": d.get("scale_factor"),
                "rungs": (len(certified), len(ladder)),
                "peak_per_s": max(rates) if rates else None,
                "buckets": len(merged),
                "peak_procs": max(procs) if procs else None,
                "campaign_s": d.get("campaign_s"),
            }
        )
    return rows


def print_flagship(rows) -> None:
    print("\nflagship campaigns (flagship-*.json):")
    print(
        f"{'topology':>12} {'cert_max':>8} {'scale_x':>8} {'rungs':>6} "
        f"{'peak/s':>8} {'buckets':>7} {'procs':>5} {'wall_s':>7}  artifact"
    )
    for r in rows:
        topo = (
            f"{r['frontends']}fx{r['shards']}sx{r['replicas']}r"
            if None not in (r["frontends"], r["shards"], r["replicas"])
            else "-"
        )
        rungs = f"{r['rungs'][0]}/{r['rungs'][1]}"
        peak = f"{r['peak_per_s']:.1f}" if r["peak_per_s"] is not None else "-"
        print(
            f"{topo:>12} "
            f"{r['certified_max'] if r['certified_max'] is not None else '-':>8} "
            f"{r['scale_factor'] if r['scale_factor'] is not None else '-':>8} "
            f"{rungs:>6} "
            f"{peak:>8} "
            f"{r['buckets']:>7} "
            f"{r['peak_procs'] if r['peak_procs'] is not None else '-':>5} "
            f"{r['campaign_s'] if r['campaign_s'] is not None else '-':>7}  "
            f"{r['artifact']}"
        )


def load_arrivals_ab(artdir: pathlib.Path):
    """One row per flagship-*.json campaign carrying the within-run
    arrivals A/B (scripts/flagship.py): the serial and pipelined
    rung.arrivals walls at the same cohort on the same live plane, the
    drift-immune speedup ratio bench_compare gates, and both legs'
    exactness flags."""
    rows = []
    for f in sorted(artdir.glob("flagship-*.json")):
        try:
            d = json.loads(f.read_text())
        except (OSError, ValueError):
            continue
        ab = d.get("arrivals_ab") if isinstance(d, dict) else None
        if not isinstance(ab, dict):
            continue
        legs = ab.get("legs") if isinstance(ab.get("legs"), dict) else {}
        serial = legs.get("serial") if isinstance(legs.get("serial"), dict) else {}
        pipe = (
            legs.get("pipelined")
            if isinstance(legs.get("pipelined"), dict) else {}
        )
        rows.append(
            {
                "artifact": f.name,
                "cohort": ab.get("cohort"),
                "serial_s": serial.get("arrivals_s"),
                "pipelined_s": pipe.get("arrivals_s"),
                "speedup": ab.get("arrivals_pipeline_speedup"),
                "churned": (serial.get("churned"), pipe.get("churned")),
                "exact": (
                    serial.get("exact") and serial.get("flat_byte_match")
                    and pipe.get("exact") and pipe.get("flat_byte_match")
                ),
            }
        )
    return rows


def print_arrivals_ab(rows) -> None:
    print("\narrivals ingest A/B (serial vs pipelined, flagship-*.json):")
    print(
        f"{'cohort':>7} {'serial_s':>9} {'pipe_s':>8} {'speedup':>8} "
        f"{'churned':>9} {'exact':>5}  artifact"
    )
    for r in rows:
        churned = (
            f"{r['churned'][0]}/{r['churned'][1]}"
            if None not in r["churned"] else "-"
        )
        exact = "-" if r["exact"] is None else ("yes" if r["exact"] else "NO")
        print(
            f"{r['cohort'] if r['cohort'] is not None else '-':>7} "
            f"{r['serial_s'] if r['serial_s'] is not None else '-':>9} "
            f"{r['pipelined_s'] if r['pipelined_s'] is not None else '-':>8} "
            f"{r['speedup'] if r['speedup'] is not None else '-':>8} "
            f"{churned:>9} {exact:>5}  {r['artifact']}"
        )


def load_tier_close_ab(artdir: pathlib.Path):
    """One row per flagship-*.json campaign carrying the within-run
    tier-close A/B (scripts/flagship.py): the SDA_TIER_FANOUT=1 serial
    and default-fanout post-ingest tier walls (all tier.* stages —
    falling back to tier.close alone for older artifacts) at the same
    cohort on the same live plane, the drift-immune
    ``tier_close_fanout_speedup`` ratio bench_compare gates, the fanout
    leg's lane occupancy, and both legs' exactness flags."""
    rows = []
    for f in sorted(artdir.glob("flagship-*.json")):
        try:
            d = json.loads(f.read_text())
        except (OSError, ValueError):
            continue
        ab = d.get("tier_close_ab") if isinstance(d, dict) else None
        if not isinstance(ab, dict):
            continue
        legs = ab.get("legs") if isinstance(ab.get("legs"), dict) else {}
        serial = legs.get("serial") if isinstance(legs.get("serial"), dict) else {}
        fan = legs.get("fanout") if isinstance(legs.get("fanout"), dict) else {}
        rows.append(
            {
                "artifact": f.name,
                "cohort": ab.get("cohort"),
                "serial_s": serial.get("tier_s", serial.get("tier_close_s")),
                "fanout_s": fan.get("tier_s", fan.get("tier_close_s")),
                "speedup": ab.get("tier_close_fanout_speedup"),
                "overlap": fan.get("overlap_efficiency"),
                "exact": (
                    serial.get("exact") and serial.get("flat_byte_match")
                    and fan.get("exact") and fan.get("flat_byte_match")
                ),
            }
        )
    return rows


def print_tier_close_ab(rows) -> None:
    print("\ntier close A/B (serial vs fanned-out siblings, flagship-*.json):")
    print(
        f"{'cohort':>7} {'serial_s':>9} {'fanout_s':>9} {'speedup':>8} "
        f"{'overlap':>8} {'exact':>5}  artifact"
    )
    for r in rows:
        exact = "-" if r["exact"] is None else ("yes" if r["exact"] else "NO")
        print(
            f"{r['cohort'] if r['cohort'] is not None else '-':>7} "
            f"{r['serial_s'] if r['serial_s'] is not None else '-':>9} "
            f"{r['fanout_s'] if r['fanout_s'] is not None else '-':>9} "
            f"{r['speedup'] if r['speedup'] is not None else '-':>8} "
            f"{r['overlap'] if r['overlap'] is not None else '-':>8} "
            f"{exact:>5}  {r['artifact']}"
        )


def load_sketch(artdir: pathlib.Path):
    """One row per sketch family per wire dimension per sketch-*.json
    artifact (bench.py's measure_sketch_accuracy): the accuracy-vs-
    dimension trend — observed error vs analytic bound, headroom, and
    the end-to-end secure-round throughput."""
    rows = []
    for f in sorted(artdir.glob("sketch-*.json")):
        try:
            d = json.loads(f.read_text())
        except (OSError, ValueError):
            continue
        fams = d.get("families") if isinstance(d, dict) else None
        if not isinstance(fams, dict):
            continue
        for fam, body in sorted(fams.items()):
            legs = body.get("legs") if isinstance(body, dict) else None
            if not isinstance(legs, dict):
                continue
            # ascending wire dimension, so each family reads as a trend
            for tag, leg in sorted(
                legs.items(), key=lambda kv: (kv[1] or {}).get("dim") or 0
            ):
                if not isinstance(leg, dict) or leg.get("dim") is None:
                    continue
                rows.append(
                    {
                        "artifact": f.name,
                        "family": fam,
                        "tag": tag,
                        "dim": leg.get("dim"),
                        # countmin legs carry max_err, cardinality abs_err
                        "err": (
                            leg.get("max_err")
                            if leg.get("max_err") is not None
                            else leg.get("abs_err")
                        ),
                        "bound": leg.get("bound"),
                        "headroom": leg.get("bound_headroom"),
                        "within": leg.get("within_bound"),
                        "items_per_s": leg.get("items_per_s"),
                        "exact": leg.get("byte_exact"),
                    }
                )
    return rows


def print_sketch(rows) -> None:
    print("\nsketch-accuracy riders (sketch-*.json):")
    print(
        f"{'family':>12} {'leg':>6} {'dim':>6} {'err':>8} {'bound':>8} "
        f"{'headroom':>8} {'in_bnd':>6} {'items/s':>8} {'exact':>5}  artifact"
    )
    for r in rows:
        within = "-" if r["within"] is None else ("yes" if r["within"] else "NO")
        exact = "-" if r["exact"] is None else ("yes" if r["exact"] else "NO")
        print(
            f"{r['family']:>12} {r['tag']:>6} {r['dim']:>6} "
            f"{r['err'] if r['err'] is not None else '-':>8} "
            f"{r['bound'] if r['bound'] is not None else '-':>8} "
            f"{r['headroom'] if r['headroom'] is not None else '-':>8} "
            f"{within:>6} "
            f"{r['items_per_s'] if r['items_per_s'] is not None else '-':>8} "
            f"{exact:>5}  {r['artifact']}"
        )


def load_scenarios(artdir: pathlib.Path):
    """Latest record per (scenario, store, transport) cell from the churn
    harness's scenario-*.json artifacts (scripts/scenarios.py), plus any
    overhead-ab-*.json retry-layer A/B records."""
    cells: dict = {}
    for f in sorted(artdir.glob("scenario-*.json")):
        try:
            d = json.loads(f.read_text())
        except (OSError, ValueError):
            continue
        if not isinstance(d, dict) or not all(
            k in d for k in ("scenario", "store", "transport", "ok")
        ):
            continue
        # sorted() walks stamps ascending, so the last write wins = latest
        cells[(d["scenario"], d["store"], d["transport"])] = {
            "artifact": f.name,
            "ok": bool(d["ok"]),
            "exact": bool(d.get("exact")),
            "error": d.get("error"),
        }
    overheads = []
    for f in sorted(artdir.glob("overhead-ab-*.json")):
        try:
            d = json.loads(f.read_text())
        except (OSError, ValueError):
            continue
        if isinstance(d, dict) and d.get("overhead_pct") is not None:
            overheads.append({"artifact": f.name, **d})
    return cells, overheads


def print_scenarios(cells, overheads) -> None:
    """The survivability matrix: scenario rows x (store, transport)
    columns, latest artifact per cell; '--' = cell never run."""
    print("\nchurn-scenario survivability (scenario-*.json, latest per cell):")
    scenarios = sorted({k[0] for k in cells})
    cols = sorted({(k[1], k[2]) for k in cells})
    header = " ".join(f"{s[:4]}/{t[:4]:<4}" for s, t in cols)
    print(f"{'scenario':<28} {header}")
    for name in scenarios:
        row = []
        for s, t in cols:
            cell = cells.get((name, s, t))
            row.append("--" if cell is None else ("OK" if cell["ok"] else "FAIL"))
        print(f"{name:<28} " + " ".join(f"{c:<9}" for c in row))
    bad = [(k, c) for k, c in cells.items() if not c["ok"]]
    if bad:
        print("failing cells:")
        for (name, s, t), c in bad:
            print(f"  {name} [{s}/{t}]: {c['error']}  ({c['artifact']})")
    else:
        print(f"all {len(cells)} banked cells green")
    for o in overheads:
        print(
            f"retry-layer overhead A/B: {o['overhead_pct']:+.2f}% over "
            f"{o.get('requests_per_arm', '?')} requests/arm "
            f"({'OK' if o.get('ok') else 'OVER BOUND'})  ({o['artifact']})"
        )


def main() -> int:
    artdir = pathlib.Path(sys.argv[1] if len(sys.argv) > 1 else "bench-artifacts")
    ingest_rows = load_ingest(artdir)
    clerking_rows = load_clerking(artdir)
    reveal_rows = load_reveal(artdir)
    committee_rows = load_committee(artdir)
    wire_rows = load_wire(artdir)
    tier_rows = load_tier(artdir)
    promotion_rows = load_promotion_ab(artdir)
    soak_rows = load_soak(artdir)
    flagship_rows = load_flagship(artdir)
    arrivals_rows = load_arrivals_ab(artdir)
    tier_close_rows = load_tier_close_ab(artdir)
    sketch_rows = load_sketch(artdir)
    scenario_cells, overhead_rows = load_scenarios(artdir)
    if (
        not ingest_rows
        and not clerking_rows
        and not reveal_rows
        and not committee_rows
        and not wire_rows
        and not tier_rows
        and not soak_rows
        and not flagship_rows
        and not sketch_rows
        and not scenario_cells
    ):
        print(
            f"no rate-bearing ingest-*.json, clerking-*.json, "
            f"reveal-*.json, committee-*.json, wire-*.json, tier-*.json, "
            f"soak-*.json, flagship-*.json, sketch-*.json, or "
            f"scenario-*.json artifacts under {artdir}/",
            file=sys.stderr,
        )
        return 1

    if ingest_rows:
        print_ingest(ingest_rows)
    if clerking_rows:
        print_clerking(clerking_rows)
    if reveal_rows:
        print_reveal(reveal_rows)
    if committee_rows:
        print_committee(committee_rows)
    if wire_rows:
        print_wire(wire_rows)
    if tier_rows:
        print_tier(tier_rows)
    if promotion_rows:
        print_promotion_ab(promotion_rows)
    if soak_rows:
        print_soak(soak_rows)
    if flagship_rows:
        print_flagship(flagship_rows)
    if arrivals_rows:
        print_arrivals_ab(arrivals_rows)
    if tier_close_rows:
        print_tier_close_ab(tier_close_rows)
    if sketch_rows:
        print_sketch(sketch_rows)
    if scenario_cells:
        print_scenarios(scenario_cells, overhead_rows)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
