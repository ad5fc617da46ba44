"""The chip contract, rehearsed on the CPU.

``chip_smoke.py`` is what the driver runs on the TPU; its leg functions
take their sizes as arguments so this file can call them at tiny sizes
with interpret-mode kernels. Also pinned here: the device plane chooses
its implementation from the backend it can observe (never by catching a
failure), and the compile cache can be placed from outside.
"""

import ast
import inspect
import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


# -- chip_smoke.py ------------------------------------------------------------


def test_chip_smoke_refuses_a_cpu():
    """Run as a script it accepts no CPU — not even an explicitly pinned
    one: non-zero exit, and no result line on stdout."""
    out = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 2, (out.returncode, out.stderr[-800:])
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout
    assert "leg ok" not in out.stdout.replace("build leg ok", "")


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """Without the program beside it the script fails and prints no result."""
    (tmp_path / "chip_smoke.py").write_text((REPO / "chip_smoke.py").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path, env=env,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_chip_smoke_legs_at_tiny_sizes(monkeypatch, capsys):
    """Every device leg, in this process, on the 8-device CPU mesh."""
    import chip_smoke
    from sda_tpu.crypto.masking import ChaChaMasker

    device = chip_smoke.device_line(allow_pinned_cpu=True)
    assert device == {"platform": "cpu", "kind": "cpu", "count": 8}

    # sized below the device-combine threshold the leg refuses to run: the
    # reveal would never reach the device plane
    with pytest.raises(chip_smoke.SmokeFailure, match="threshold"):
        chip_smoke.protocol_leg(dim=40, participants=6)
    monkeypatch.setattr(ChaChaMasker, "DEVICE_COMBINE_THRESHOLD", 1)
    chip_smoke.protocol_leg(dim=40, participants=6)
    chip_smoke.fabric_leg(
        dim=60, chunk=100, participants=400,
        preset_dim=60, preset_chunk=100, preset_participants=400, seeds=4,
    )
    chip_smoke.sharded_leg(dim=40, rows_per_shard=4)

    out = capsys.readouterr().out
    assert "mask combine on jnp, reveal exact" in out  # the CPU's path
    assert "7 of 8 clerks" in out  # a committee member really was dropped
    for engine in ("sumfirst 61-bit", "participant 31-bit", "participant+pallas 31-bit"):
        assert f"fabric leg ok: {engine}" in out
    assert "sharded leg ok: 8 devices, six dry-run fabrics" in out
    # information only: nothing under a metric name, no JSON metric line
    assert "shared_elements_per_second" not in out
    assert not [ln for ln in out.splitlines() if ln.startswith("{")]


def test_chip_smoke_sharded_leg_names_its_skip(monkeypatch, capsys):
    import jax

    import chip_smoke

    monkeypatch.setattr(jax, "devices", lambda *a: [object()])
    chip_smoke.sharded_leg()
    assert capsys.readouterr().out.strip() == "sharded leg skipped: 1 device"


def test_chip_smoke_starts_one_child_before_jax():
    """One process per chip: the only child is the build, and it runs
    before JAX is imported; no leg shells out to bench or the dry run."""
    tree = ast.parse((REPO / "chip_smoke.py").read_text())
    spawns = [
        node
        for node in ast.walk(tree)
        if isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id in ("subprocess", "os", "multiprocessing")
        and node.attr in ("run", "Popen", "call", "check_call", "check_output",
                          "system", "fork", "Process", "Pool")
    ]
    assert len(spawns) == 1
    build = next(n for n in tree.body if getattr(n, "name", "") == "build_leg")
    assert build.lineno <= spawns[0].lineno <= build.end_lineno
    main = next(n for n in tree.body if getattr(n, "name", "") == "main")
    calls = [
        n.func.id for n in ast.walk(main)
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
    ]
    assert calls.index("build_leg") < calls.index("device_line")
    # nothing at module level imports jax (the build child must come first)
    top_imports = [
        alias.name
        for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))
        for alias in n.names
    ] + [n.module for n in tree.body if isinstance(n, ast.ImportFrom)]
    assert not [m for m in top_imports if m and m.split(".")[0] in ("jax", "bench")]


# -- no fallback that hides the device ----------------------------------------


def _excepts(obj) -> list:
    import textwrap

    tree = ast.parse(textwrap.dedent(inspect.getsource(obj)))
    return [n for n in ast.walk(tree) if isinstance(n, ast.ExceptHandler)]


def test_chacha_backend_is_a_function_of_the_backend(monkeypatch):
    """TPU -> the compiled kernel, anything else -> jnp; decided by
    ``jax.default_backend()`` alone, with no ``except`` anywhere in the
    module — a kernel that fails to compile on the chip raises."""
    import jax

    from sda_tpu.ops import chacha_pallas

    assert chacha_pallas.default_backend() == "jnp"  # this suite runs on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert chacha_pallas.default_backend() == "pallas"
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert chacha_pallas.default_backend() == "jnp"
    assert _excepts(chacha_pallas) == []
    assert not hasattr(chacha_pallas, "pallas_available")


def test_compiled_kernel_failure_propagates(monkeypatch):
    """On this CPU the compiled kernel cannot run (Pallas offers only its
    interpreter here). Told the backend is a TPU, the device combine must
    raise that — and so must ``ChaChaMasker.combine`` above its threshold,
    not quietly run the host loop."""
    import jax
    import numpy as np

    from sda_tpu.crypto import masking
    from sda_tpu.ops import chacha_pallas

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seeds = np.arange(8, dtype=np.uint32).reshape(2, 4)
    with pytest.raises(ValueError, match="interpret mode"):
        chacha_pallas.combine_masks_device(seeds, 19, 433)

    assert _excepts(masking.ChaChaMasker) == []
    monkeypatch.setattr(masking.ChaChaMasker, "DEVICE_COMBINE_THRESHOLD", 1)
    masker = masking.ChaChaMasker(433, 21, 128)
    with pytest.raises(ValueError, match="interpret mode"):
        masker.combine([row.astype(np.int64) for row in seeds])


def test_limb_kernel_interpret_is_explicit():
    """The fused limb kernel never decides by itself to interpret."""
    from sda_tpu.parallel import limb_pallas

    source = inspect.getsource(limb_pallas)
    assert "default_backend" not in source
    for fn in (limb_pallas.participant_limb_sums_pallas,
               limb_pallas.share_combine_limb_pallas):
        param = inspect.signature(fn).parameters["interpret"]
        assert param.default is False and param.kind is param.KEYWORD_ONLY


# -- a compile cache that can be placed from outside ---------------------------


def _cache_dir_after_init(env_overrides: dict) -> dict:
    code = (
        "import json, jax\n"
        "before = jax.config.jax_compilation_cache_dir\n"
        "from sda_tpu.ops.jaxcfg import ensure_x64\n"
        "ensure_x64()\n"
        "print(json.dumps({'before': before,"
        " 'after': jax.config.jax_compilation_cache_dir}))\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_overrides, PYTHONPATH=str(REPO))
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=120, env=env, cwd="/",
    )
    assert out.returncode == 0, out.stderr[-800:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_dir_from_env_is_left_alone(tmp_path):
    got = _cache_dir_after_init({"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert got["before"] == got["after"] == str(tmp_path)


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout():
    got = _cache_dir_after_init({})
    assert got["before"] is None
    assert got["after"] == str(REPO / ".jax_cache")
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def test_no_other_place_sets_a_cache_path():
    hits = [
        str(path.relative_to(REPO))
        for path in [*REPO.glob("*.py"), *REPO.glob("sda_tpu/**/*.py"),
                     *REPO.glob("scripts/*.py"), *REPO.glob("examples/*.py")]
        if "compilation_cache_dir" in path.read_text()
    ]
    assert hits == ["sda_tpu/ops/jaxcfg.py"]


# -- kernel bodies stay 32-bit under x64 ----------------------------------------


def _pallas_body_dtypes(fn, *args) -> set:
    import jax
    from jax.extend.core import Literal

    def walk(jaxpr, inside, found):
        for eqn in jaxpr.eqns:
            now_inside = inside or eqn.primitive.name == "pallas_call"
            if inside:
                # literal operands (static ref indices trace as weak int64
                # scalars, which Mosaic folds) are not values of the body
                found.update(
                    str(v.aval.dtype)
                    for v in [*eqn.invars, *eqn.outvars]
                    if not isinstance(v, Literal) and hasattr(v.aval, "dtype")
                )
            for param in eqn.params.values():
                for sub in param if isinstance(param, (list, tuple)) else [param]:
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner, now_inside, found)
        return found

    return walk(jax.make_jaxpr(fn)(*args).jaxpr, False, set())


def test_no_64_bit_types_inside_kernel_bodies():
    """Both kernels are traced under ``jax_enable_x64``; Mosaic rejects
    64-bit types, and only the chip would say so. Walk the traced kernel
    bodies instead (test_pallas_lint.py guards the index maps)."""
    import jax.numpy as jnp
    import numpy as np

    from sda_tpu.ops import chacha_pallas
    from sda_tpu.ops.jaxcfg import ensure_x64
    from sda_tpu.parallel.limb_pallas import participant_limb_sums_pallas
    from sda_tpu.parallel.limbmatmul import fold_const_limbs

    ensure_x64()
    chacha = _pallas_body_dtypes(
        lambda st: chacha_pallas._rounds_pallas(st, interpret=True),
        jnp.zeros((700, 16), jnp.uint32),
    )
    p = (1 << 31) - 1
    stacks = fold_const_limbs(np.arange(56).reshape(7, 8) % p, p)
    limb = _pallas_body_dtypes(
        lambda v: participant_limb_sums_pallas(v, stacks, interpret=True),
        jnp.zeros((6, 7, 300), jnp.int32),
    )
    assert chacha and limb
    for found in (chacha, limb):
        assert not [d for d in found if "64" in d], found
