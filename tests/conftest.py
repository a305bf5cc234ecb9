"""Test rig: force the host-CPU backend with 8 virtual devices.

This is the analog of the reference's CPU_ONLY cmake fallback
(reference: libccaffe/CMakeLists.txt:44-47) — it lets every test, including
the multi-chip collective paths, run with no TPU attached (SURVEY.md §4.3).
Must run before jax initializes its backends, hence the env mutation at
import time of conftest.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
# entry points called in-process (time_net.main, the apps) turn the
# persistent compile cache on for their checkout; the test session itself
# writes none
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest

REFERENCE = "/root/reference"


def reference_path(rel: str = "") -> str:
    """A path under the reference tree (the Caffe sources this repository
    was modelled on).  Skips the calling test when the tree is absent: it
    is not part of the repository."""
    if not os.path.isdir(REFERENCE):
        pytest.skip(f"reference tree {REFERENCE} is absent")
    return os.path.join(REFERENCE, rel)


@pytest.fixture
def rng():
    import jax
    return jax.random.PRNGKey(0)


@pytest.fixture
def np_rng():
    return np.random.default_rng(0)


_MP_PROBE: dict = {"result": None}


@pytest.fixture(scope="session")
def multiprocess_cpu() -> bool:
    """Whether this rig's CPU backend supports multiprocess XLA
    computations.  Some jax builds reject them outright ('Multiprocess
    computations aren't implemented on the CPU backend'); the multi-host
    and multi-process chaos tests skip there instead of failing on an
    environment limitation.  Probed once per session with a minimal
    2-process driver run."""
    if _MP_PROBE["result"] is None:
        import subprocess
        import sys
        import tempfile

        from sparknet_tpu.tools.launch import launch_local

        driver = os.path.join(os.path.dirname(__file__),
                              "multihost_driver.py")
        saved = dict(os.environ)
        os.environ.pop("XLA_FLAGS", None)   # this conftest's 8-device flag
        for k in list(os.environ):
            if k.startswith("SPARKNET_"):
                os.environ.pop(k)
        try:
            with tempfile.TemporaryDirectory() as td:
                rc = launch_local(
                    [sys.executable, driver, "--strategy", "sync",
                     "--out", os.path.join(td, "probe.npz"),
                     "--rounds", "1"],
                    nprocs=2, platform="cpu", devices_per_proc=2,
                    timeout=240)
        finally:
            os.environ.clear()
            os.environ.update(saved)
        _MP_PROBE["result"] = rc == 0
    return _MP_PROBE["result"]
