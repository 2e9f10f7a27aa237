"""JAX backend start-up for the serving runtimes.

The platform is whatever stock ``JAX_PLATFORMS`` asks for (unset = JAX's own
choice).  The runtimes call `apply_platform_override()` first thing: it
joins the multi-host slice when configured, initializes the backend NOW —
so a replica that asked for an accelerator and did not get one fails at
start instead of serving from whatever JAX finds — and logs the device it
will serve from.
"""

from __future__ import annotations

import os

from ..logging import logger


def apply_platform_override() -> None:
    """Initialize the requested backend or raise.  No retry into
    auto-select and no swallowed init error: a pod scheduled onto a TPU
    that comes up on the CPU would pass its probes and serve at a
    fraction of the speed it is billed for."""
    import jax

    from .distributed import maybe_initialize_distributed

    # multi-host: join the slice BEFORE backend init (jax.distributed must
    # precede the first device query)
    maybe_initialize_distributed()
    # raises when a platform JAX_PLATFORMS names cannot be initialized
    devices = jax.devices()
    logger.info(
        "JAX backend: platform=%s device_kind=%s devices=%d "
        "(JAX_PLATFORMS=%r, jax %s)",
        devices[0].platform, devices[0].device_kind, len(devices),
        os.environ.get("JAX_PLATFORMS", ""), jax.__version__)
