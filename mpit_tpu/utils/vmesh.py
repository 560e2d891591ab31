"""Virtual host-mesh provisioning for CPU runs (tests, examples, dry runs).

JAX reads ``JAX_PLATFORMS`` once, at import, and XLA reads ``XLA_FLAGS``
once, at backend init.  Code that picks the platform or the device count
*after* ``import jax`` (tests/conftest.py, the examples that call
:func:`force_virtual_devices`) therefore has to go through the config API
and must run before the first computation.  That recipe lives here only.

Importing this module is safe pre-backend-init: the package ``__init__`` pulls
in jax but runs no computation.
"""

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"
# XLA:CPU collectives run one thread per virtual device and abort the whole
# process (SIGABRT, "Termination timeout ... Exiting to ensure a consistent
# program state", rendezvous.cc) if any participant misses the rendezvous
# within the default 40 s. On a loaded box 8 device threads can legitimately
# take longer to all get scheduled — raise the ceiling so slow is slow, not
# dead. 120 s tolerates slow scheduling without turning a genuine deadlock
# (see parallel/common.bound_cpu_dispatch, the actual mitigation) into a
# 15-minute hang. The flag is registered in the pinned jaxlib (pyproject.toml).
_RENDEZVOUS_FLAG = "--xla_cpu_collective_call_terminate_timeout_seconds"
_RENDEZVOUS_SECONDS = 120


def repin_platform(platform: str) -> None:
    """Choose jax's platform after ``import jax``.

    JAX reads ``JAX_PLATFORMS`` at import, so setting the variable later
    changes nothing on its own; the config API does, as long as no backend
    has been initialized yet (the choice is sticky afterwards). The
    environment is updated too, for child processes."""
    import jax

    os.environ["JAX_PLATFORMS"] = platform
    jax.config.update("jax_platforms", platform)


def force_virtual_devices(n: int, platform: str = "cpu") -> None:
    """Expose an ``n``-device virtual host mesh on ``platform``.

    Replaces any pre-existing device-count flag (CI images sometimes set
    one).  Call before backend init.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    for flag in (_COUNT_FLAG, _RENDEZVOUS_FLAG):
        flags = re.sub(flag + r"=\d+", "", flags)
    extra = f"{_COUNT_FLAG}={n} {_RENDEZVOUS_FLAG}={_RENDEZVOUS_SECONDS}"
    os.environ["XLA_FLAGS"] = " ".join((flags + " " + extra).split())
    repin_platform(platform)
