"""CLI acceptance: run the walkthrough script end-to-end over a real server
process and assert the documented expected output (reference:
docs/simple-cli-example.sh, README.md:157)."""

import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_simple_cli_example():
    env = dict(os.environ)
    env["SDA_PORT"] = "18871"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        ["sh", str(REPO / "scripts" / "simple-cli-example.sh")],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "result: 0 2 2 4 4 6 6 8 8 10" in proc.stdout, proc.stdout


def _cpu_bench_env():
    """Explicit-CPU env for bench subprocesses (conftest already pinned
    JAX_PLATFORMS=cpu and switched the persistent compile cache off)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # ambient overrides (e.g. left exported while iterating on bench)
    # must not change which code path each test exercises
    env.pop("SDA_BENCH_DEADLINE", None)
    env.pop("SDA_BENCH_INJECT_FAULT", None)
    env.pop("SDA_FAULTS", None)
    # test subprocesses must not litter bench-artifacts/
    env["SDA_BENCH_ARTIFACTS"] = "0"
    # the protocol-plane riders drive full REST rounds (~30s per child on
    # one core) and nothing here reads their output — every assertion in
    # this file is about the device metric line and the failure
    # contracts, so the bench children skip the riders
    env["SDA_BENCH_RIDERS"] = "0"
    return env


_TINY = ["--participants", "2000", "--dim", "60", "--chunk", "1000"]


def _bench(env, *extra, timeout=240):
    return subprocess.run(
        [sys.executable, str(REPO / "bench.py"), *_TINY, *extra],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=timeout,
    )


def test_bench_cpu_smoke_all_engines():
    """The driver's bench entry must never rot: run every engine path at
    tiny sizes on CPU (subprocess, so the forced-cpu env doesn't leak) and
    require the self-verification line plus a well-formed JSON metric
    carrying the crypto-plane rates and the device parity evidence."""
    import json

    env = _cpu_bench_env()
    # --quick pins the narrow 31-bit sumfirst branch (the bare default
    # would force --wide and duplicate that case); the --check variants
    # cover the reduced/skipped independent-verification modes on both
    # the narrow and the wide (uint32-pair) sumfirst paths. The probe
    # variants override --dim to 2100 (argparse: last flag wins) so
    # check_stride is 2 and dim % stride != 0 — the strided-subset
    # slicing and its finalize alignment really execute; at dim 60 the
    # stride would be 1 and probe would be byte-identical to full.
    # The parity routine does the same work whatever the engine flags
    # say, so it rides three children (both engines, both dims) and the
    # rest skip it.
    for extra in (
        ["--quick"],
        ["--wide", "--no-parity"],
        ["--engine", "participant"],
        ["--quick", "--check", "probe", "--dim", "2100", "--no-parity"],
        ["--wide", "--check", "probe", "--dim", "2100"],
        ["--wide", "--check", "off", "--no-parity"],
        # the rbg generator variant must stay runnable end-to-end, not
        # just flag-parse
        ["--wide", "--rng", "rbg", "--no-parity"],
        # the roofline decomposition: two extra variant compiles, stage
        # fractions, binding stage — on both engines (participant names
        # its stage share_combine)
        ["--wide", "--roofline", "--no-parity"],
        ["--engine", "participant", "--roofline", "--no-parity"],
    ):
        out = _bench(env, *extra)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "verified" in out.stderr
        line = json.loads(out.stdout.strip().splitlines()[-1])
        assert line["unit"] == "shared_elements_per_second"
        assert line["value"] > 0
        assert line["crypto"]["seals_per_s"] > 0
        if "--no-parity" in extra:
            assert "tpu_parity" not in line
        else:
            parity = line["tpu_parity"]
            assert parity["ok"] is True, parity
            # on the CPU: the jnp twin that backend uses + the kernel
            # source under the interpreter — chosen from the backend,
            # nothing caught
            assert parity["chacha_backends"] == ["jnp", "interpret"]
            assert parity["chacha_jnp"] == parity["chacha_interpret"] == "ok"
            assert parity["limb"] == parity["wide61"] == "ok"
        # every metric line names the device it ran on ...
        assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
        if "--check" in extra:
            mode = extra[extra.index("--check") + 1]
            assert line["check"] == mode
            if mode == "probe":
                # dim 2100 -> stride 2 -> ceil(2100/2) covered columns;
                # strictly fewer than dim proves the subset path ran
                assert line["check_cols"] == 1050 < line["dim"]
        if "--rng" in extra:
            assert line["rng"] == extra[extra.index("--rng") + 1]
        # ... and a device kind with no entry in bench.DEVICE_PEAKS gets
        # the modeled traffic but no percent-of-peak field of any chip
        roof = line["roofline"]
        assert roof["hbm_gbps_model"] > 0
        assert not [f for f in roof if "pct" in f or "v5e" in f], roof
        if "--engine" in extra:
            assert roof["int8_tops"] > 0  # participant engine: MXU work modeled
        if "--roofline" in extra:
            decomp = roof["decomposition"]
            stage3 = "share_combine" if "participant" in extra else "limb_reduce"
            assert decomp["binding_stage"] in ("check", "rng_expand", stage3)
            # at this test's microsecond segment times the stage fractions
            # are noise-dominated, so only shape is pinned, not values
            for f in ("frac_check", "frac_rng_expand", f"frac_{stage3}"):
                assert decomp[f] >= 0.0, decomp
            assert decomp["seg_nocheck_s"] >= 0 and decomp["seg_fill_s"] >= 0


def test_bench_verification_catches_injected_fault():
    """The self-verification must be able to FAIL, not just bless good
    runs: with one accumulator cell corrupted via the SDA_BENCH_INJECT_FAULT
    hook, the independent plaintext check has to reject the stream, exit 1,
    and still print one well-formed error-tagged metric line."""
    import json

    env = _cpu_bench_env()
    env["SDA_BENCH_INJECT_FAULT"] = "acc"
    for extra in (["--quick"], ["--wide"]):  # narrow and pair check paths
        out = _bench(env, "--no-parity", *extra)
        assert out.returncode == 1, (out.returncode, out.stderr[-500:])
        assert "VERIFICATION FAILED" in out.stderr
        line = json.loads(out.stdout.strip().splitlines()[-1])
        assert line["value"] == 0 and "verification failed" in line["error"]
        assert line["device"]["platform"] == "cpu"


def test_bench_parity_failure_is_fatal():
    """A kernel whose bits differ from its reference ends the run: exit
    non-zero, error-tagged line, no throughput value — never a headline
    that carries `ok: false` beside it."""
    import json

    env = _cpu_bench_env()
    env["SDA_BENCH_INJECT_FAULT"] = "parity"
    out = _bench(env, "--quick")
    assert out.returncode == 2, (out.returncode, out.stderr[-500:])
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["value"] == 0 and "ParityError" in line["error"]
    assert "verified" not in out.stderr  # the measurement never started


def test_bench_without_tpu_or_cpu_pin_reports_nothing():
    """No TPU, and nobody asked for the CPU: JAX quietly falls back to a
    CpuDevice, and bench must call that a failure — non-zero exit before
    any metric line, nothing on stdout that carries a `value`."""
    env = _cpu_bench_env()
    del env["JAX_PLATFORMS"]
    out = _bench(env, "--quick")
    assert out.returncode == 2, (out.returncode, out.stderr[-500:])
    assert "no TPU" in out.stderr
    assert "value" not in out.stdout, out.stdout
    assert out.stdout.strip() == ""


def test_bench_crash_emits_error_metric():
    """Once a device is held the metric-line contract covers *exceptions*:
    a crash inside the pipeline (here: a chunk beyond the narrow
    reduction's exact bound) still produces ONE error-tagged JSON metric
    line and exit 2 — never a raw traceback on stdout."""
    import json

    env = _cpu_bench_env()
    out = _bench(
        env, "--engine", "participant", "--pallas", "--no-parity",
        "--participants", "4000000", "--chunk", "4000000", "--dim", "5",
    )
    assert out.returncode == 2, (out.returncode, out.stderr[-500:])
    stdout_lines = out.stdout.strip().splitlines()
    for raw in stdout_lines:
        json.loads(raw)
    line = json.loads(stdout_lines[-1])
    assert line["value"] == 0 and line["vs_baseline"] == 0.0
    assert "overflows int32" in line["error"]
    assert "Traceback" in out.stderr  # diagnosis preserved on stderr


def test_bench_deadline_emits_error_metric():
    """The pre-measurement watchdog contract: when nothing can be
    measured in time, bench still prints ONE well-formed, error-tagged
    JSON metric line and exits 2 — never hangs silently."""
    import json

    env = _cpu_bench_env()
    out = _bench(env, "--quick", "--deadline", "0.2")
    assert out.returncode == 2, (out.returncode, out.stderr[-500:])
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["value"] == 0 and "deadline" in line["error"]
    assert "DEADLINE" in out.stderr


def test_rest_ingest_script_sqlite():
    """scripts/rest_ingest.py (the sustained REST+sqlite ingest
    measurement, VERDICT r4 #6) at a small n: the transcript setup
    replays, every POST is accepted, the stored row count is re-verified
    through the store, and the artifact carries the measured rate."""
    import json

    env = _cpu_bench_env()
    out = subprocess.run(
        [
            sys.executable, str(REPO / "scripts" / "rest_ingest.py"),
            "--n", "300", "--threads", "3", "--backend", "sqlite",
        ],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=240,
    )
    assert out.returncode == 0, out.stderr[-1500:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["backend"] == "sqlite" and line["n"] == 300
    assert line["stored_rows_verified"] is True
    assert line["participations_per_s"] > 0
    assert sum(w["ok"] for w in line["per_worker"]) == 300
