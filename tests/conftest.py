"""Test harness: force JAX onto a virtual 8-device CPU platform so
multi-chip sharding logic is exercised without TPU hardware
(SURVEY.md §4: cluster-in-a-box testing pattern)."""

import pathlib
import subprocess
import sys

import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
try:  # prefer the installed package (pip install -e .)
    import persia_tpu  # noqa: F401
except ImportError:  # bare checkout fallback
    sys.path.insert(0, str(REPO_ROOT))

# Tests always run on the virtual 8-device CPU mesh, wherever they are
# started. PERSIA_TEST_TPU=1 opts out so the TPU-gated hardware-validation
# tests (the compiled Pallas kernel check) can reach the chip — run them
# through the chip tool, one pytest process holding the chip.
import os  # noqa: E402

from persia_tpu.utils import force_cpu_platform  # noqa: E402

if os.environ.get("PERSIA_TEST_TPU") != "1":
    force_cpu_platform(8)
else:
    # Chip-touching pytest runs get the same two-tier in-process
    # watchdog as chip_smoke.py: a hung compile exits non-zero with stacks
    # dumped instead of hanging the harness.
    #
    # The watchdog is RE-ARMED before every test rather than armed once
    # for the session: a single budget sized for one hung compile
    # (default 1500s) used to hard-kill healthy suite runs that simply
    # had many tests (>25 min total). Per-test re-arming bounds any one
    # hung test by the budget while letting an N-test suite run
    # N x budget in the healthy case.
    from persia_tpu.utils import arm_watchdog

    _WD_SEC = int(os.environ.get("PERSIA_TPU_WATCHDOG_SEC", "1500"))
    # collection itself (imports may touch the backend) gets one budget
    _wd_cancel = arm_watchdog(_WD_SEC,
                              label="pytest[PERSIA_TEST_TPU] collection")

    @pytest.fixture(autouse=True)
    def _rearm_tpu_watchdog(request):
        global _wd_cancel
        _wd_cancel()
        _wd_cancel = arm_watchdog(
            _WD_SEC, label=f"pytest[PERSIA_TEST_TPU] {request.node.name}")
        yield


@pytest.fixture(scope="session")
def native_lib_path():
    """Build (if needed) and return the native shared library path."""
    build_dir = REPO_ROOT / "native" / "build"
    lib = build_dir / "libpersia_native.so"
    makefile = REPO_ROOT / "native" / "Makefile"
    if makefile.exists():
        subprocess.run(
            ["make", "-C", str(REPO_ROOT / "native"), "-j", "8"],
            check=True,
            capture_output=True,
        )
    if not lib.exists():
        pytest.skip("native library not built")
    return str(lib)
