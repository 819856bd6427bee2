"""Shared multi-device subprocess runner for the forced-host-device
tests (test_ring, test_distributed, test_dryrun_specs,
test_serve_sharded).

One definition of the subprocess environment, because its contents are
load-bearing in a way per-test copies kept getting wrong:

* ``JAX_PLATFORMS=cpu`` — without the pin jax probes for a TPU backend
  first, and on TPU-library-equipped hosts that probe retries metadata
  fetches for ~8 minutes per subprocess before falling back to CPU
  (these are CPU tests by construction);
* ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — must be set
  before jax initializes, which is the whole reason these tests run in
  a subprocess rather than the (1-device) main test process.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path
from typing import Optional

SRC = str(Path(__file__).resolve().parent.parent / "src")


def run_snippet(
    snippet: str,
    *,
    devices: Optional[int] = 8,
    timeout: int = 600,
    check: bool = True,
    env: Optional[dict] = None,
) -> subprocess.CompletedProcess:
    """Run a dedented python snippet in a pinned-env subprocess.

    ``devices=None`` omits XLA_FLAGS for snippets that set their own
    device count before importing jax. ``check=True`` asserts a zero
    exit status with stderr in the failure message. ``env`` adds
    variables to the pinned environment.
    """
    base = {"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
            "JAX_PLATFORMS": "cpu"}
    base.update({k: os.environ[k] for k in ("HOME", "TMPDIR")
                 if k in os.environ})
    env = {**base, **(env or {})}
    if devices is not None:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}"
        )
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(snippet)],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
    )
    if check:
        assert proc.returncode == 0, proc.stderr[-4000:]
    return proc
