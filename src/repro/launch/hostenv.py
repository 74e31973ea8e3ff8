"""Tuned host-environment profile (``--tuned-host``).

Large-scale JAX training launchers ship the same three host-side knobs in
their run.sh (see SNIPPETS.md 1-2: HomebrewNLP, olmax):

* ``LD_PRELOAD`` tcmalloc — the host-LRU put path is malloc-heavy (numpy
  gather/scatter temporaries every step); tcmalloc's thread caches beat
  glibc malloc on that churn.
* ``TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD`` — silence the per-allocation
  warnings numpy's big table buffers would otherwise trigger.
* ``TF_CPP_MIN_LOG_LEVEL`` / ``XLA_FLAGS`` — quiet logs and pin the host
  platform device count instead of letting XLA guess from the core count.

``LD_PRELOAD`` only takes effect at process start, so ``apply_tuned_host``
re-execs the interpreter exactly once (guarded by a marker env var). When
libtcmalloc is not installed the profile degrades to the env-var-only
subset — a graceful no-op, never an error. The re-exec must happen before
the process initialises a JAX backend: a process that has opened the TPU
holds it, and the re-exec'd image could not open it again, so
``apply_tuned_host`` refuses once a backend is up. Importing ``jax`` alone
opens nothing.
"""
from __future__ import annotations

import glob
import os
import sys

# marker: set on first application so the re-exec'd process (which inherits
# it) falls straight through instead of exec-looping
_MARKER = "REPRO_TUNED_HOST"

# the exact soname the exemplar launchers preload, then progressively
# looser fallbacks (minimal build, unversioned dev symlink, other arches)
_TCMALLOC_GLOBS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/*/libtcmalloc.so*",
    "/usr/lib/*/libtcmalloc_minimal.so*",
    "/usr/lib/libtcmalloc*.so*",
    "/usr/local/lib/libtcmalloc*.so*",
)


def find_tcmalloc() -> str | None:
    """Path of the best installed libtcmalloc, or None when absent."""
    for pat in _TCMALLOC_GLOBS:
        hits = sorted(glob.glob(pat))
        if hits:
            return hits[0]
    return None


def tuned_env(host_devices: int = 1, base_xla_flags: str = "") -> dict:
    """The env-var subset of the profile, as a pure dict (no process
    mutation — apply_tuned_host and the benchmark A/B both consume this).
    ``base_xla_flags`` is merged so caller-set XLA flags survive."""
    flag = f"--xla_force_host_platform_device_count={int(host_devices)}"
    flags = base_xla_flags
    if "--xla_force_host_platform_device_count" not in flags:
        flags = f"{flags} {flag}".strip()
    return {
        "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
        "TF_CPP_MIN_LOG_LEVEL": "4",
        "XLA_FLAGS": flags,
    }


def apply_tuned_host(host_devices: int = 1) -> str:
    """Apply the profile to THIS process. Returns a status string:

    * ``"already"``     — marker set (we are the re-exec'd process);
    * ``"no-tcmalloc"`` — env vars applied, libtcmalloc absent (no-op
      degradation: nothing to preload, no re-exec);
    * ``"preloaded"``   — env vars applied, tcmalloc already in LD_PRELOAD.

    When tcmalloc is found and not yet preloaded this re-execs the
    interpreter with LD_PRELOAD set and does NOT return.
    """
    if os.environ.get(_MARKER):
        return "already"
    if "jax" in sys.modules:
        from jax._src import xla_bridge
        if xla_bridge.backends_are_initialized():
            raise RuntimeError(
                "--tuned-host must be applied before JAX initialises a "
                "backend: this process may already hold the accelerator")
    os.environ.update(tuned_env(host_devices,
                                os.environ.get("XLA_FLAGS", "")))
    os.environ[_MARKER] = "1"
    lib = find_tcmalloc()
    if lib is None:
        return "no-tcmalloc"
    pre = os.environ.get("LD_PRELOAD", "")
    if lib in pre.split(":"):
        return "preloaded"
    os.environ["LD_PRELOAD"] = f"{lib}:{pre}" if pre else lib
    # sys.argv[0] is the script path under both `python x.py` and
    # `python -m pkg.mod`; PYTHONPATH is inherited so imports resolve
    os.execv(sys.executable, [sys.executable] + sys.argv)
    raise AssertionError("unreachable")  # pragma: no cover
