"""The pieces that let the program run on the chip, checked on the CPU:
``chip_smoke.py``'s platform gate, the compile-cache helper, and the
rule that the service subprocesses never touch JAX (a chip belongs to
one process: a PS or worker child that imported JAX would fight the
trainer for it)."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
SMOKE = REPO_ROOT / "chip_smoke.py"


def _run(args, cwd=REPO_ROOT, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR",
                         "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS")}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "ok" in json.loads(lines[-1])
    except ValueError:
        return False


def test_result_line_has_exactly_the_contract_keys():
    """The driver refuses a last line with any other key: the per-phase
    detail belongs on the summary line before it."""
    sys.path.insert(0, str(REPO_ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = chip_smoke.result_line(device)
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": device}
    src = SMOKE.read_text()
    # it is the last thing main() writes before it returns 0
    assert src.count("result_line(device)") == 2
    assert "log(result_line(device))\n    return 0\n" in src


def test_smoke_refuses_cpu_and_names_the_platform():
    r = _run([str(SMOKE)])
    assert r.returncode != 0
    assert "platform: cpu" in r.stdout
    assert "needs a TPU" in r.stderr
    assert not _has_result(r.stdout)
    assert "phase" not in r.stdout  # the gate comes before any work


def test_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program: past the gate (--tiny is the only
    way there on a CPU) the first persia_tpu import must end it."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run(["chip_smoke.py", "--tiny"], cwd=tmp_path, PYTHONPATH="")
    assert r.returncode != 0
    assert "persia_tpu" in r.stderr
    assert not _has_result(r.stdout)


_CACHE_PROBE = (
    "import json, jax; from persia_tpu.utils import enable_compile_cache; "
    "before = jax.config.jax_compilation_cache_dir; "
    "got = enable_compile_cache(); "
    "print(json.dumps([before, got, jax.config.jax_compilation_cache_dir, "
    "jax.config.jax_persistent_cache_min_compile_time_secs]))")


def _probe_cache(**env):
    r = _run(["-c", _CACHE_PROBE], **env)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compile_cache_helper_default_is_a_fixed_path_in_the_checkout():
    seen = set()
    for _ in range(2):  # two processes, one answer
        before, got, after, min_secs = _probe_cache()
        assert before is None
        assert got == after == str(REPO_ROOT / ".jax_cache")
        assert min_secs == 0.0  # sub-second compilations are cached too
        seen.add(got)
    assert len(seen) == 1


def test_compile_cache_helper_leaves_env_choice_alone(tmp_path):
    before, got, after, min_secs = _probe_cache(
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="2.5")
    # jax read both variables itself; the helper reports the directory
    # and overrides neither
    assert before == got == after == str(tmp_path)
    assert min_secs == 2.5


def test_compile_cache_helper_sets_nothing_outside_a_checkout(tmp_path):
    """An installed copy (no pyproject.toml beside the package) must not
    write into its parent directory — that would be site-packages."""
    pkg = tmp_path / "persia_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    shutil.copy(REPO_ROOT / "persia_tpu" / "utils.py", pkg / "utils.py")
    r = _run(["-c", _CACHE_PROBE], cwd=tmp_path, PYTHONPATH=str(tmp_path))
    assert r.returncode == 0, r.stderr
    before, got, after, min_secs = json.loads(
        r.stdout.strip().splitlines()[-1])
    assert before is None and got is None and after is None
    assert min_secs != 0.0  # threshold untouched as well
    assert not (tmp_path / ".jax_cache").exists()


def test_service_modules_do_not_import_jax():
    mods = ["persia_tpu.service.ps_service",
            "persia_tpu.service.worker_service",
            "persia_tpu.service.coordinator",
            "persia_tpu.service.helper",
            "persia_tpu.launcher",
            "persia_tpu.pipeline",
            "persia_tpu.data.dataloader"]
    code = ("import sys\n"
            + "".join(f"import {m}\n" for m in mods)
            + "bad = sorted(m for m in sys.modules "
              "if m == 'jax' or m.startswith(('jax.', 'jaxlib')))\n"
              "print(bad)\nsys.exit(1 if bad else 0)\n")
    r = _run(["-c", code])
    assert r.returncode == 0, (r.stdout, r.stderr)
