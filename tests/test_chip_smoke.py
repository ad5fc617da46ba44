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


#: the fabric leg at sizes the CPU folds in a second
TINY = {"dim": 60, "chunk": 100}


def _protocol_leg(chip_smoke, monkeypatch):
    from sda_tpu.crypto.masking import ChaChaMasker

    # sized below the device-combine threshold the leg refuses to run: the
    # reveal would never reach the device plane
    with pytest.raises(chip_smoke.SmokeFailure, match="threshold"):
        chip_smoke.protocol_leg(dim=40, participants=6)
    monkeypatch.setattr(ChaChaMasker, "DEVICE_COMBINE_THRESHOLD", 1)
    chip_smoke.protocol_leg(dim=40, participants=6)
    # the CPU's path, and a committee member really was dropped
    return ["mask combine on jnp, reveal exact", "7 of 8 clerks"]


def _engine_leg(engine, bits):
    def run(chip_smoke, monkeypatch):
        chip_smoke.fold_engine(engine, **TINY)
        return [
            f"fabric leg ok: {engine} {bits}-bit, 200 rows x dim 60 in 2 chunks, "
            "default draw, reveal from 7 of 8 clerks exact"
        ]

    return run


def _sharded_leg(chip_smoke, monkeypatch):
    chip_smoke.sharded_leg(dim=40, rows_per_shard=4)
    return ["sharded leg ok: 8 devices, six dry-run fabrics"]


LEGS = {
    "protocol": _protocol_leg,
    "sumfirst": _engine_leg("sumfirst", 61),
    "participant": _engine_leg("participant", 31),
    "participant+xla": _engine_leg("participant+xla", 31),
    "participant+pallas": _engine_leg("participant+pallas", 31),
    "sharded": _sharded_leg,
}


@pytest.mark.parametrize("leg", sorted(LEGS))
def test_chip_smoke_legs_at_tiny_sizes(leg, monkeypatch, capsys):
    """Every device leg, and every engine of the fabric leg, in this
    process, on the 8-device CPU mesh."""
    import chip_smoke

    device = chip_smoke.device_line(allow_pinned_cpu=True)
    assert device == {"platform": "cpu", "kind": "cpu", "count": 8}
    said = LEGS[leg](chip_smoke, monkeypatch)
    out = capsys.readouterr().out
    for line in said:
        assert line in out
    # information only: a leg prints no JSON line, so nothing reads as a metric
    assert not [ln for ln in out.splitlines() if ln.startswith("{")]


def test_the_fabric_leg_holds_parity_and_the_two_participant_paths_to_one_accumulator(
    monkeypatch, capsys
):
    """The whole leg: kernel parity on the CPU's backends (the jnp twin and
    the kernel source under the interpreter, chosen from the backend,
    nothing caught), the engines, and the accumulators of the cell's entry
    and of the Pallas kernel held to the named XLA formulation's: a kernel
    path that leaves other bits fails the leg though its own reveal is
    exact."""
    import chip_smoke

    chip_smoke.fabric_leg(**TINY, preset_dim=60, preset_chunk=100, seeds=4)
    out = capsys.readouterr().out
    assert "'chacha_backends': ['jnp', 'interpret']" in out
    for name in ("chacha_jnp", "chacha_interpret", "limb", "wide61"):
        assert f"'{name}': 'ok'" in out
    assert out.count("fabric leg ok: ") == 4

    fold = chip_smoke.fold_engine
    monkeypatch.setattr(
        chip_smoke, "fold_engine",
        lambda engine, **sizes: fold(engine, **sizes) + (engine.endswith("pallas")),
    )
    monkeypatch.setattr(chip_smoke, "kernel_parity", lambda **sizes: {"ok": True})
    with pytest.raises(chip_smoke.SmokeFailure, match="differs from the XLA"):
        chip_smoke.fabric_leg(**TINY, preset_dim=60, preset_chunk=100, seeds=4)


@pytest.mark.parametrize("engine,module,epilogue,cell", [
    # (L, B, K): the low limb of the first secret column's sum
    ("sumfirst", "sumfirst", "clerk_sums_from_limb_acc", 0),
    # (W, B, n): a share sum of clerk 7, one of the seven that reveal
    ("participant", "limbmatmul", "limb_recombine_host", -1),
    ("participant+xla", "limbmatmul", "limb_recombine_host", -1),
    ("participant+pallas", "limbmatmul", "limb_recombine_host", -1),
])
def test_a_corrupted_accumulator_fails_the_fabric_leg(
    engine, module, epilogue, cell, monkeypatch
):
    """The leg's self-check can fail, not only bless: one cell of the
    accumulator off by one on its way into the epilogue the leg calls, and
    the reveal no longer matches the exact column sums."""
    import importlib

    import chip_smoke

    owner = importlib.import_module(f"sda_tpu.parallel.{module}")
    real = getattr(owner, epilogue)

    def corrupted(acc, *rest):
        acc = acc.copy()
        acc[(cell,) * acc.ndim] += 1
        return real(acc, *rest)

    monkeypatch.setattr(owner, epilogue, corrupted)
    with pytest.raises(chip_smoke.SmokeFailure, match="exact column sums"):
        chip_smoke.fold_engine(engine, **TINY)


@pytest.mark.parametrize("kernel,module,function", [
    ("chacha_jnp", "sda_tpu.ops.chacha_pallas", "combine_masks_device"),
    ("limb", "sda_tpu.parallel.limb_pallas", "share_combine_limb_pallas"),
])
def test_a_kernel_whose_bits_differ_fails_the_parity(
    kernel, module, function, monkeypatch
):
    """A mismatch is fatal, not a note: one element of the kernel's output
    off by one and ``kernel_parity`` raises, naming the kernel."""
    import importlib

    import numpy as np

    import chip_smoke

    owner = importlib.import_module(module)
    real = getattr(owner, function)

    def off_by_one(*args, **kwargs):
        out = real(*args, **kwargs)
        hot = np.zeros(out.shape, dtype=np.int64)
        hot.flat[0] = 1
        return out + hot

    monkeypatch.setattr(owner, function, off_by_one)
    with pytest.raises(chip_smoke.SmokeFailure, match=f"{kernel}: device bits differ"):
        chip_smoke.kernel_parity(seeds=4, dim=60, chunk=100, limb_dim=60)


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
        lambda seeds: chacha_pallas._rounds_pallas(seeds, 700, 5, interpret=True),
        jnp.zeros((9, 4), jnp.uint32),
    )
    p = (1 << 31) - 1
    stacks = fold_const_limbs(np.arange(56).reshape(7, 8) % p, p)
    limb = _pallas_body_dtypes(
        lambda c, r: participant_limb_sums_pallas(c, r, stacks, interpret=True),
        jnp.zeros((300 * 5, 6), jnp.int32),
        jnp.zeros((2, 300, 6), jnp.int32),
    )
    assert chacha and limb
    for found in (chacha, limb):
        assert not [d for d in found if "64" in d], found
