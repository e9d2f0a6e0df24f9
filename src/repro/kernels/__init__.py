"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships three files:

  <name>/kernel.py - pl.pallas_call + explicit BlockSpec VMEM tiling
  <name>/ops.py    - jit'd public wrapper (host packing, backend dispatch)
  <name>/ref.py    - pure-jnp oracle, used by tests and as the CPU path

On a TPU the kernels compile through Mosaic.  Anywhere else they run in
interpret mode only when that is asked for explicitly with
``REPRO_PALLAS_INTERPRET=1`` (the test suite and CPU rehearsals set it);
otherwise a Pallas call off the TPU raises instead of quietly simulating
the kernel.  The engine takes its backend from ``backend=``; the legacy
``REPRO_USE_PALLAS`` switch only steers the non-engine kernel wrappers.
"""

import os

#: Opt-in for running Pallas kernels in interpret mode off the TPU.
INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"


def use_pallas() -> bool:
    return os.environ.get("REPRO_USE_PALLAS", "0") == "1"


def interpret_mode() -> bool:
    """False on a TPU; True elsewhere when ``REPRO_PALLAS_INTERPRET=1``.

    Raises on any other platform, so a Pallas run can never land on the
    interpreter because the accelerator was missing.
    """
    import jax

    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if os.environ.get(INTERPRET_ENV) == "1":
        return True
    raise RuntimeError(
        f"Pallas kernels need a TPU but jax runs on {platform!r}; set "
        f"{INTERPRET_ENV}=1 to run them in interpret mode"
    )
