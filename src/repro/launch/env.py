"""Launch environment harness: allocator + XLA flags, applied BEFORE jax.

Step timings are only comparable when the process environment is pinned:
the allocator (tcmalloc vs glibc malloc changes host-staging cost), XLA's
logging noise, whether the backend preallocates its arena (the OOM-trial
ladder in ``repro.tuner.max_batch`` needs it OFF so a failed trial's blocks
actually return), and the step markers profilers key on.  This module is
the Python half of that contract — ``scripts/launch_env.sh`` is the shell
half (it additionally LD_PRELOADs tcmalloc, which a running interpreter
cannot) — and both set the same variables, defaulting but never clobbering:
anything the user already exported wins.

Import-order matters: XLA reads these at backend init, so call
``apply_env()`` before the first ``import jax`` (``benchmarks/run.py`` and
``repro.launch.dryrun`` do).  This module therefore must not import jax.
"""
from __future__ import annotations

import os
import platform
import sys
import warnings

# flag -> default value; merged into XLA_FLAGS only when the flag is absent
XLA_FLAG_DEFAULTS: dict[str, str] = {}

# TPU-only flags: the CPU/GPU wheels' env-flag parser does not know these
# DebugOptions and ABORTS the process on unknown flags (parse_flags_from_env
# check-fails), so they must never reach a non-TPU run
TPU_XLA_FLAG_DEFAULTS = {
    # 1 = mark steps at the outer while loop (0 marks program entry):
    # profilers and the step-time gate then bracket exactly one logical
    # step per marker (HomebrewNLP run.sh uses the same setting)
    "--xla_step_marker_location": "1",
}

ENV_DEFAULTS = {
    # let the OOM-trial retry ladder actually reclaim a failed trial's
    # arena instead of probing a preallocated (and thus opaque) pool
    "XLA_PYTHON_CLIENT_PREALLOCATE": "false",
    # silence libtf/XLA info chatter that skews wall-clock on slow ttys
    "TF_CPP_MIN_LOG_LEVEL": "4",
    # tcmalloc (when preloaded by scripts/launch_env.sh): only report
    # truly pathological single allocations, not every large weight buffer
    "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
}


def merge_xla_flags(flags: dict[str, str]) -> str:
    """Fold ``flags`` into ``XLA_FLAGS``, keeping any user-set values.

    A flag already present in the env (with any value) is left alone —
    the merge only appends missing ones.  Returns the merged string (also
    written back to ``os.environ``).
    """
    current = os.environ.get("XLA_FLAGS", "")
    parts = current.split()
    for flag, value in flags.items():
        if not any(p == flag or p.startswith(flag + "=") for p in parts):
            parts.append(f"{flag}={value}" if value is not None else flag)
    merged = " ".join(parts)
    os.environ["XLA_FLAGS"] = merged
    return merged


def apply_env(host_devices: int | None = None) -> None:
    """Pin the launch environment (idempotent; user-set values win).

    ``host_devices`` adds ``--xla_force_host_platform_device_count`` for
    multi-device dry runs on a single host.  Warns (but proceeds) when jax
    is already imported — the backend has then read its config and most of
    these settings are inert for this process.
    """
    if "jax" in sys.modules:
        warnings.warn(
            "repro.launch.env.apply_env() called after jax was imported; "
            "XLA flags set now will not reach the already-initialized "
            "backend", stacklevel=2,
        )
    for key, value in ENV_DEFAULTS.items():
        os.environ.setdefault(key, value)
    flags = dict(XLA_FLAG_DEFAULTS)
    # the TPU flags need a pinned platform: with JAX_PLATFORMS unset the
    # backend is unknown until jax initializes, and a CPU backend aborts
    # on them
    if _pinned_platform() == "tpu":
        flags.update(TPU_XLA_FLAG_DEFAULTS)
    if host_devices is not None:
        flags["--xla_force_host_platform_device_count"] = str(host_devices)
    merge_xla_flags(flags)


def _pinned_platform() -> str:
    """The platform ``JAX_PLATFORMS`` pins, or ``unknown`` when unset."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0] or "unknown"


def host_fingerprint() -> str:
    """Coarse same-host-class tag stamped into bench rows.

    ``machine-cpucount-backend`` (e.g. ``x86_64-8-cpu``): two rows with
    equal fingerprints were produced on comparable hosts, so the step-time
    gate may compare them; rows from different classes never pair.  Once
    jax is imported the backend is jax's own answer; before that it is the
    ``JAX_PLATFORMS`` pin, or ``unknown``.
    """
    backend = (
        sys.modules["jax"].default_backend() if "jax" in sys.modules
        else _pinned_platform()
    )
    return f"{platform.machine()}-{os.cpu_count()}-{backend}"
