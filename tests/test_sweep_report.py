"""scripts/sweep_report.py — the host riders' artifact summarizer: one
section per rider, broken artifacts excluded, an empty directory an error.
"""

import importlib.util
import json
import pathlib
import sys

_spec = importlib.util.spec_from_file_location(
    "sweep_report",
    pathlib.Path(__file__).resolve().parent.parent / "scripts" / "sweep_report.py",
)
sweep_report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(sweep_report)


def _write(d, name, obj):
    (d / name).write_text(json.dumps(obj))


def test_ingest_rider_section(tmp_path, capsys):
    _write(tmp_path, "ingest-20260805-010000.json",
           {"metric": "batched_participation_ingest",
            "seal_batch_per_s": 40000, "build_per_s": 800,
            "participate_many_per_s": 900, "rest_sqlite_batch_per_s": 8000,
            "rest_mem_batch_per_s": 10000, "telemetry_overhead_pct": 1.2})
    _write(tmp_path, "ingest-old-20260731.json",
           {"seal_batch_per_s": 12000})  # pre-telemetry artifact: kept, gaps dashed
    _write(tmp_path, "ingest-broken.json", {"note": "no rates"})  # excluded
    old = sys.argv
    sys.argv = ["sweep_report.py", str(tmp_path)]
    try:
        # ingest rows alone are evidence: exit 0 by themselves
        assert sweep_report.main() == 0
    finally:
        sys.argv = old
    out = capsys.readouterr().out
    assert "batched-ingest riders" in out
    assert "ingest-20260805-010000.json" in out
    assert "ingest-old-20260731.json" in out
    assert "ingest-broken.json" not in out


def test_clerking_rider_section(tmp_path, capsys):
    _write(tmp_path, "clerking-20260805-020000.json",
           {"metric": "clerking_pipeline",
            "config": {"n_participants": 6000, "clerks": 2},
            "configs": {
                "monolithic": {"encryptions_per_s": 20000, "wall_s": 0.3,
                               "peak_rss_mib": 86.0, "chunk_size": None,
                               "overlap_efficiency": None},
                "chunked_4096": {"encryptions_per_s": 18000, "wall_s": 0.33,
                                 "peak_rss_mib": 68.4, "chunk_size": 4096,
                                 "overlap_efficiency": 0.93,
                                 "vs_monolithic": 0.9}}})
    _write(tmp_path, "clerking-broken.json", {"note": "no configs"})  # excluded
    old = sys.argv
    sys.argv = ["sweep_report.py", str(tmp_path)]
    try:
        # clerking rows alone are evidence: exit 0 by themselves
        assert sweep_report.main() == 0
    finally:
        sys.argv = old
    out = capsys.readouterr().out
    assert "clerking-pipeline riders" in out
    assert "clerking-20260805-020000.json" in out
    assert "monolithic" in out and "chunked_4096" in out
    assert "0.93" in out  # overlap efficiency column
    assert "clerking-broken.json" not in out


def test_reveal_rider_section(tmp_path, capsys):
    _write(tmp_path, "reveal-20260805-030000.json",
           {"metric": "reveal_pipeline",
            "config": {"clerks": 2, "dim": 32},
            "configs": {
                "monolithic_4096": {"encryptions_per_s": 26000, "wall_s": 0.2,
                                    "peak_rss_mib": 92.0, "chunk_size": None,
                                    "n_participants": 4096,
                                    "overlap_efficiency": None},
                "chunked_4096": {"encryptions_per_s": 24000, "wall_s": 0.22,
                                 "peak_rss_mib": 61.5, "chunk_size": 1024,
                                 "n_participants": 4096,
                                 "overlap_efficiency": 0.88,
                                 "vs_monolithic": 0.92}}})
    _write(tmp_path, "reveal-broken.json", {"note": "no configs"})  # excluded
    old = sys.argv
    sys.argv = ["sweep_report.py", str(tmp_path)]
    try:
        # reveal rows alone are evidence: exit 0 by themselves
        assert sweep_report.main() == 0
    finally:
        sys.argv = old
    out = capsys.readouterr().out
    assert "reveal-pipeline riders" in out
    assert "reveal-20260805-030000.json" in out
    assert "monolithic_4096" in out and "chunked_4096" in out
    assert "0.88" in out  # overlap efficiency column
    assert "reveal-broken.json" not in out


def test_committee_rider_section(tmp_path, capsys):
    _write(tmp_path, "committee-20260805-040000.json",
           {"metric": "committee_scaling",
            "config": {"n_participants": 4000, "clerks": 2},
            "cpu_count": 4,
            "planes": {
                "clerking": {
                    "w1": {"workers": 1, "per_s": 9000, "wall_s": 0.44,
                           "peak_rss_mib": 70.0, "vs_w1": 1.0,
                           "identical_to_serial": True},
                    "w4": {"workers": 4, "per_s": 27000, "wall_s": 0.15,
                           "peak_rss_mib": 71.0, "vs_w1": 3.0,
                           "identical_to_serial": True}},
                "reveal": {
                    "w1": {"workers": 1, "per_s": 8000, "wall_s": 0.5,
                           "peak_rss_mib": 66.0, "vs_w1": 1.0,
                           "identical_to_serial": True}}},
            "read_pool": {
                "t1": {"threads": 1, "reads_per_s": 20.0, "vs_t1": 1.0},
                "t4": {"threads": 4, "reads_per_s": 76.0, "vs_t1": 3.8}}})
    _write(tmp_path, "committee-broken.json", {"note": "no planes"})  # excluded
    old = sys.argv
    sys.argv = ["sweep_report.py", str(tmp_path)]
    try:
        # committee rows alone are evidence: exit 0 by themselves
        assert sweep_report.main() == 0
    finally:
        sys.argv = old
    out = capsys.readouterr().out
    assert "committee-scaling riders" in out
    assert "committee-20260805-040000.json" in out
    assert "clerking" in out and "read_pool" in out
    # scaling efficiency = vs_w1 / workers: 3.0x on 4 workers -> 0.75, and
    # the read-pool probe's 3.8x on 4 threads -> 0.95
    assert "0.75" in out and "0.95" in out
    assert "committee-broken.json" not in out


def test_wire_rider_section(tmp_path, capsys):
    _write(tmp_path, "wire-20260805-060000.json",
           {"metric": "wire_transport", "n_participants": 3000,
            "chunk_size": 512, "store": "mem",
            "json": {"ingest_per_s": 17616, "clerking_fetch_per_s": 133333,
                     "reveal_per_s": 22305, "peak_rss_mib": 75.5},
            "binary": {"ingest_per_s": 59524, "clerking_fetch_per_s": 181818,
                       "reveal_per_s": 22676, "peak_rss_mib": 68.5},
            "json_baseline_per_s": 11000,
            "ingest_binary_vs_baseline": 5.41,
            "ingest_binary_vs_json": 3.38,
            "clerking_fetch_binary_vs_json": 1.36,
            "reveal_binary_vs_json": 1.02,
            "rss_flat": True})
    # legacy shape without the baseline columns: kept, gaps dashed
    _write(tmp_path, "wire-20260805-050000.json",
           {"metric": "wire_transport",
            "binary": {"ingest_per_s": 40000}})
    _write(tmp_path, "wire-broken.json", {"note": "no legs"})  # excluded
    old = sys.argv
    sys.argv = ["sweep_report.py", str(tmp_path)]
    try:
        # wire rows alone are evidence: exit 0 by themselves
        assert sweep_report.main() == 0
    finally:
        sys.argv = old
    out = capsys.readouterr().out
    assert "wire-transport riders" in out
    assert "wire-20260805-060000.json" in out
    assert "59524" in out and "17616" in out  # both legs' ingest rates
    assert "5.41" in out  # the acceptance ratio vs the recorded baseline
    assert "flat" in out
    assert "wire-20260805-050000.json" in out  # legacy row kept, dashed
    assert "wire-broken.json" not in out


def test_tier_rider_section(tmp_path, capsys):
    _write(tmp_path, "tier-20260806-010000.json",
           {"metric": "tier_fanout",
            "config": {"n_participants": 48, "fanouts": [2, 4, 8],
                       "tiers": 2, "cpu_count": 1},
            "configs": {
                "flat": {"fanout": None, "exact": True, "wall_s": 0.68,
                         "nodes": 1, "max_job_participations": 48,
                         "per_job_stage_s": 0.0068,
                         "inputs_per_clerk_s": 3529},
                "m4": {"fanout": 4, "exact": True, "wall_s": 0.7,
                       "nodes": 5, "max_job_participations": 15,
                       "vs_flat_max_job": 0.312, "vs_flat_wall": 1.03,
                       "per_job_stage_s": 0.00084,
                       "inputs_per_clerk_s": 6667},
                "m2": {"fanout": 2, "exact": True, "wall_s": 0.59,
                       "nodes": 3, "max_job_participations": 27,
                       "vs_flat_max_job": 0.562, "vs_flat_wall": 0.86,
                       "per_job_stage_s": 0.00101,
                       "inputs_per_clerk_s": 8571}}})
    _write(tmp_path, "tier-broken.json", {"note": "no configs"})  # excluded
    old = sys.argv
    sys.argv = ["sweep_report.py", str(tmp_path)]
    try:
        # tier rows alone are evidence: exit 0 by themselves
        assert sweep_report.main() == 0
    finally:
        sys.argv = old
    out = capsys.readouterr().out
    assert "tier-fanout riders" in out
    assert "tier-20260806-010000.json" in out
    assert "tier-broken.json" not in out
    # flat baseline leads, then fan-outs ascending (not lexicographic)
    lines = [ln for ln in out.splitlines() if "tier-20260806-010000" in ln]
    assert [ln.split()[0] for ln in lines] == ["flat", "m2", "m4"]
    assert "0.312" in out   # per-clerk bound ratio vs flat
    assert "0.00084" in out  # mean stage seconds per clerk job


def test_soak_rider_section(tmp_path, capsys):
    _write(tmp_path, "soak-20260806-010000.json",
           {"kind": "soak",
            "config": {"duration_s": 60.0, "rate": 40.0, "round_size": 80},
            "total_rounds": 12, "exact_rounds": 12,
            "samples": [{"t": 1.0}, {"t": 2.0}, {"t": 3.0}],
            "sampler_overhead_pct": 0.84,
            "summary": {"rps_mean": 55.7, "rps_max": 65.6,
                        "p99_s_by_route": {
                            "aggregations/participations":
                                {"max": 0.021, "last": 0.012},
                            "ping": {"max": 0.002, "last": 0.001}},
                        "rss_mib": {"start": 45.0, "end": 46.5,
                                    "peak": 47.1}}})
    _write(tmp_path, "soak-broken.json", {"note": "not a soak record"})
    old = sys.argv
    sys.argv = ["sweep_report.py", str(tmp_path)]
    try:
        # soak rows alone are evidence: exit 0 by themselves
        assert sweep_report.main() == 0
    finally:
        sys.argv = old
    out = capsys.readouterr().out
    assert "sustained-soak riders" in out
    assert "soak-20260806-010000.json" in out
    assert "all" in out          # every round exact collapses to "all"
    assert "0.0210s" in out      # worst p99 belongs to the hottest route
    assert "45.0->47.1" in out   # RSS start->peak trajectory
    assert "+0.84" in out        # sampler overhead column
    assert "soak-broken.json" not in out


def test_scenario_survivability_section(tmp_path, capsys):
    _write(tmp_path, "scenario-vanish-after-sharing-20260805-050000-mem-rest.json",
           {"scenario": "vanish-after-sharing", "store": "mem",
            "transport": "rest", "ok": False, "exact": False,
            "error": "boom (stale run)"})
    # same cell, later stamp: latest record wins, so the cell turns green
    _write(tmp_path, "scenario-vanish-after-sharing-20260805-060000-mem-rest.json",
           {"scenario": "vanish-after-sharing", "store": "mem",
            "transport": "rest", "ok": True, "exact": True, "error": None})
    _write(tmp_path, "scenario-clerk-kill-mid-chunk-20260805-050000-sqlite-rest.json",
           {"scenario": "clerk-kill-mid-chunk", "store": "sqlite",
            "transport": "rest", "ok": False, "exact": False,
            "error": "resurrected clerk found no job"})
    _write(tmp_path, "scenario-broken-20260805.json", {"note": "no keys"})  # excluded
    _write(tmp_path, "overhead-ab-20260805-050000.json",
           {"overhead_pct": -0.10, "requests_per_arm": 1000, "ok": True})

    cells, overheads = sweep_report.load_scenarios(tmp_path)
    assert len(cells) == 2 and len(overheads) == 1
    assert cells[("vanish-after-sharing", "mem", "rest")]["ok"] is True
    assert cells[("clerk-kill-mid-chunk", "sqlite", "rest")]["ok"] is False

    old = sys.argv
    sys.argv = ["sweep_report.py", str(tmp_path)]
    try:
        # scenario rows alone are evidence: exit 0 by themselves
        assert sweep_report.main() == 0
    finally:
        sys.argv = old
    out = capsys.readouterr().out
    assert "churn-scenario survivability" in out
    assert "vanish-after-sharing" in out and "clerk-kill-mid-chunk" in out
    # vanish row: mem/rest green, sqlite/rest never run -> dashed
    assert "OK" in out and "--" in out
    assert "resurrected clerk found no job" in out  # failing-cell detail
    assert "retry-layer overhead A/B: -0.10%" in out and "1000 requests/arm" in out


def test_flagship_campaign_section(tmp_path, capsys):
    _write(tmp_path, "flagship-20260806-010000.json",
           {"kind": "flagship",
            "topology": {"frontend_processes": 3, "shards": 2,
                         "replicas": 2, "tiers": 2, "fanout": 4},
            "trace": "base=300,burst=0.25@6,churn=0.15:64",
            "simulated_population": 1_000_000,
            "certified_max_cohort": 512, "scale_factor": 1953.1,
            "ladder": [
                {"rung": 0, "cohort": 256, "round_s": 8.0,
                 "certified": True},
                {"rung": 1, "cohort": 512, "round_s": 16.0,
                 "certified": True},
                {"rung": 2, "cohort": 1024, "round_s": 90.0,
                 "certified": False},
            ],
            "merged_samples": [{"t": 1.0, "procs": 2}, {"t": 2.0, "procs": 3}],
            "campaign_s": 41.5})
    _write(tmp_path, "flagship-broken.json", {"note": "not a campaign"})
    # the grow-soak variant rides the soak section via its own glob
    _write(tmp_path, "grow-soak-20260806-010000.json",
           {"kind": "soak",
            "config": {"duration_s": 30.0, "rate": 20.0},
            "total_rounds": 4, "exact_rounds": 4,
            "samples": [{"t": 1.0}],
            "summary": {"rps_mean": 21.0, "rps_max": 25.0,
                        "rss_mib": {"start": 40.0, "end": 41.0,
                                    "peak": 41.5}}})
    old = sys.argv
    sys.argv = ["sweep_report.py", str(tmp_path)]
    try:
        assert sweep_report.main() == 0
    finally:
        sys.argv = old
    out = capsys.readouterr().out
    assert "flagship campaigns" in out
    assert "3fx2sx2r" in out     # topology collapses to NfxKsxRr
    assert "512" in out          # the certified-cohort headline
    assert "2/3" in out          # rungs certified / attempted
    assert "32.0" in out         # peak certified cohort/s = 512/16.0
    assert "flagship-broken.json" not in out
    assert "grow-soak-20260806-010000.json" in out  # soak section variant


def test_arrivals_ab_section(tmp_path, capsys):
    _write(tmp_path, "flagship-20260807-010000.json",
           {"kind": "flagship",
            "topology": {"frontend_processes": 3, "shards": 2,
                         "replicas": 2, "tiers": 2, "fanout": 4},
            "certified_max_cohort": 512,
            "ladder": [{"rung": 0, "cohort": 512, "round_s": 9.0,
                        "certified": True, "ingest_pipeline": True}],
            "arrivals_ab": {
                "cohort": 512,
                "legs": {
                    "serial": {"arrivals_s": 14.6, "round_s": 22.1,
                               "churned": 70, "exact": True,
                               "flat_byte_match": True},
                    "pipelined": {"arrivals_s": 5.2, "round_s": 12.7,
                                  "churned": 70, "exact": True,
                                  "flat_byte_match": True}},
                "arrivals_pipeline_speedup": 2.8077},
            "merged_samples": [{"t": 1.0, "procs": 2}],
            "campaign_s": 60.0})
    # a campaign without the A/B leg still rides the flagship table but
    # contributes no arrivals row
    _write(tmp_path, "flagship-20260806-090000.json",
           {"kind": "flagship",
            "topology": {"frontend_processes": 2, "shards": 2, "replicas": 2},
            "certified_max_cohort": 256, "ladder": [],
            "merged_samples": [], "campaign_s": 30.0})
    old = sys.argv
    sys.argv = ["sweep_report.py", str(tmp_path)]
    try:
        assert sweep_report.main() == 0
    finally:
        sys.argv = old
    out = capsys.readouterr().out
    assert "arrivals ingest A/B" in out
    assert "2.8077" in out          # the gated speedup ratio
    assert "14.6" in out and "5.2" in out  # both legs' arrivals walls
    assert "70/70" in out           # churn counts agree across legs
    rows = [ln for ln in out.splitlines()
            if "flagship-20260806-090000.json" in ln]
    # the A/B-less campaign appears once (flagship table), not in the
    # arrivals table
    assert len(rows) == 1


def test_tier_close_ab_section(tmp_path, capsys):
    _write(tmp_path, "flagship-20260807-020000.json",
           {"kind": "flagship",
            "topology": {"frontend_processes": 3, "shards": 2,
                         "replicas": 2, "tiers": 2, "fanout": 4},
            "certified_max_cohort": 512,
            "ladder": [{"rung": 0, "cohort": 512, "round_s": 9.0,
                        "certified": True, "ingest_pipeline": True}],
            "tier_close_ab": {
                "cohort": 512,
                "legs": {
                    # tier_s (all tier.* stages) is the compared wall;
                    # tier_close_s rides along and must NOT be the one
                    # printed when both are present
                    "serial": {"tier_s": 2.18, "tier_close_s": 0.97,
                               "round_s": 9.0,
                               "overlap_efficiency": None, "exact": True,
                               "flat_byte_match": True},
                    "fanout": {"tier_s": 1.31, "tier_close_s": 1.02,
                               "round_s": 7.9,
                               "overlap_efficiency": 0.8614, "exact": True,
                               "flat_byte_match": True}},
                "tier_close_fanout_speedup": 1.6641},
            "merged_samples": [{"t": 1.0, "procs": 2}],
            "campaign_s": 60.0})
    # a campaign without the tier A/B still rides the flagship table but
    # contributes no tier-close row
    _write(tmp_path, "flagship-20260806-080000.json",
           {"kind": "flagship",
            "topology": {"frontend_processes": 2, "shards": 2, "replicas": 2},
            "certified_max_cohort": 256, "ladder": [],
            "merged_samples": [], "campaign_s": 30.0})
    old = sys.argv
    sys.argv = ["sweep_report.py", str(tmp_path)]
    try:
        assert sweep_report.main() == 0
    finally:
        sys.argv = old
    out = capsys.readouterr().out
    assert "tier close A/B" in out
    assert "1.6641" in out          # the gated speedup ratio
    assert "2.18" in out and "1.31" in out  # both legs' tier walls
    assert "0.97" not in out        # tier_s preferred over tier_close_s
    assert "0.8614" in out          # the fanout leg's lane occupancy
    rows = [ln for ln in out.splitlines()
            if "flagship-20260806-080000.json" in ln]
    # the A/B-less campaign appears once (flagship table), not in the
    # tier-close table
    assert len(rows) == 1


def test_sketch_rider_section(tmp_path, capsys):
    _write(tmp_path, "sketch-20260806-010000.json",
           {"metric": "sketch_accuracy",
            "config": {"n_phones": 4, "seed": 20260806},
            "families": {
                "countmin": {"legs": {
                    # inserted out of dim order: the table must sort by
                    # wire dimension so each family reads as a trend
                    "w1024": {"dim": 4096, "width": 1024, "depth": 4,
                              "items_per_s": 3999, "max_err": 0.0,
                              "bound": 1.59, "within_bound": True,
                              "bound_headroom": 1.593, "byte_exact": True},
                    "w64": {"dim": 256, "width": 64, "depth": 4,
                            "items_per_s": 3243, "max_err": 7.0,
                            "bound": 25.48, "within_bound": True,
                            "bound_headroom": 3.641, "byte_exact": True}}},
                "cardinality": {"legs": {
                    "m256": {"dim": 256, "items_per_s": 3545,
                             "estimate": 220.9, "true": 200, "abs_err": 20.9,
                             "bound": 34.2, "within_bound": True,
                             "bound_headroom": 1.633, "byte_exact": True}}}}})
    _write(tmp_path, "sketch-broken.json", {"note": "no families"})  # excluded
    old = sys.argv
    sys.argv = ["sweep_report.py", str(tmp_path)]
    try:
        # sketch rows alone are evidence: exit 0 by themselves
        assert sweep_report.main() == 0
    finally:
        sys.argv = old
    out = capsys.readouterr().out
    assert "sketch-accuracy riders" in out
    assert "sketch-20260806-010000.json" in out
    assert "sketch-broken.json" not in out
    # countmin rows ascend by dim: w64 (256) before w1024 (4096)
    cm = [ln for ln in out.splitlines() if ln.strip().startswith("countmin")]
    assert [ln.split()[1] for ln in cm] == ["w64", "w1024"]
    assert "3.641" in out   # headroom column
    assert "20.9" in out    # cardinality rows surface abs_err as err
    assert "25.48" in out   # countmin rows surface bound


def test_empty_dir_is_an_error(tmp_path):
    old = sys.argv
    sys.argv = ["sweep_report.py", str(tmp_path)]
    try:
        assert sweep_report.main() == 1
    finally:
        sys.argv = old
