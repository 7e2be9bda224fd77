"""Device resolution, in the process that will use the device.

A chip belongs to one process at a time, so nothing here starts a child to
look at it: ``ensure_device()`` imports jax, places the persistent compile
cache and reads ``jax.devices()[0]``. What it finds is what the process
runs on — there is no move to host XLA behind the caller's back:

- ``--accelerator`` (``require_accelerator()``, called from ``Node.init``)
  needs a TPU. The one exception is an explicit cpu pin — ``JAX_PLATFORMS=cpu``
  in the environment or ``jax.config.jax_platforms`` set to ``cpu`` as
  tests/conftest.py does — which is honoured as the caller's choice (the
  test suite, CPU rehearsals). With no pin and no TPU it raises.
- A sweep that fails at RUN time still degrades to the oracle through
  TensorConsensus's breaker (hashgraph/accel.py); that is part of the
  product and is counted in ``accel_fallbacks``.

Also places the persistent XLA compilation cache (voting kernels compile
per window-shape bucket, so warm restarts matter): where
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and no directory
is set in code; otherwise the cache lives at ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

_lock = threading.Lock()
_resolved: Optional[str] = None

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compile_cache_dir() -> Optional[str]:
    """The directory this code must set for the persistent compile cache:
    None when ``JAX_COMPILATION_CACHE_DIR`` places it from outside (jax
    picks that up on its own), else the fixed in-checkout path. The path
    is part of the cache key, so it is never made from a temp name, a pid
    or a time."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return os.path.join(_CHECKOUT, ".jax_cache")


def setup_compile_cache(jax) -> None:
    """Shared by ensure_device() and tests/conftest.py."""
    path = compile_cache_dir()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)


def ensure_device() -> str:
    """Resolve the platform once per process: import jax, place the
    compile cache, read ``jax.devices()[0]``. Returns its platform
    ("tpu", "cpu", ...). Thread-safe."""
    global _resolved
    with _lock:
        if _resolved is None:
            import jax

            setup_compile_cache(jax)
            _resolved = jax.devices()[0].platform
            if _resolved != "cpu":
                # a bucket program compiles in well under jax's default
                # 1 s persistence threshold on the chip; cache them all
                # (host XLA keeps the default: the test suite compiles
                # thousands of tiny programs not worth an entry each)
                jax.config.update(
                    "jax_persistent_cache_min_compile_time_secs", 0.0
                )
        return _resolved


def cpu_pinned() -> bool:
    """True when host XLA is the caller's explicit choice: the FIRST
    platform of ``jax.config.jax_platforms`` (which jax seeds from
    ``JAX_PLATFORMS``) is ``cpu``. ``tpu,cpu`` is not a pin."""
    import jax

    target = jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS", "")
    return target.split(",")[0].strip() == "cpu"


def require_accelerator() -> str:
    """The ``--accelerator`` contract: a TPU, or an explicit cpu pin.
    Anything else is an error here, never a quiet run on host XLA."""
    platform = ensure_device()
    if platform != "tpu" and not cpu_pinned():
        raise RuntimeError(
            f"--accelerator needs a TPU but jax resolved {platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}). "
            "Run on a machine with a chip, drop --accelerator, or pin "
            "JAX_PLATFORMS=cpu to run the device kernels on host XLA on "
            "purpose."
        )
    return platform


def on_accelerator() -> bool:
    """True when jax dispatches to a real accelerator in this process.
    Drives the economics switches (pipelined sweeps, co-located batching,
    crossover window): on host XLA readback is synchronous and free, so
    synchronous un-batched sweeps win there."""
    return ensure_device() != "cpu"


def _is_tpu_device(dev) -> bool:
    """Shared TPU classifier for on_tpu() and describe() — one predicate so
    describe()'s capture label and the TPU-layout code paths can't drift."""
    return dev.platform == "tpu"


def on_tpu() -> bool:
    """True when the default backend is a TPU. TPU-layout-specific code
    (Pallas kernels) gates on this, not on the looser on_accelerator()."""
    return ensure_device() == "tpu"


def pallas_requested() -> bool:
    """``BABBLE_PALLAS=1`` asks for the compiled Pallas TPU kernels. Off a
    TPU that is an error, never a quiet XLA einsum (interpreter mode is a
    separate, explicit switch: ``BABBLE_PALLAS_INTERPRET=1``)."""
    if os.environ.get("BABBLE_PALLAS") != "1":
        return False
    if not on_tpu():
        raise RuntimeError(
            f"BABBLE_PALLAS=1 needs a TPU but jax resolved "
            f"{ensure_device()!r}; unset it, or set "
            "BABBLE_PALLAS_INTERPRET=1 to run the kernel in the Pallas "
            "interpreter on purpose"
        )
    return True


def describe() -> dict:
    """The live device, as evidence: every bench capture stamps this so a
    host-XLA run can never read as a TPU run. Everything derives from the
    ACTUAL ``jax.devices()``, never from configured intent."""
    import jax

    ensure_device()
    devs = jax.devices()
    dev = devs[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "count": len(devs),
        "device": str(dev),
        "capture_class": "tpu" if _is_tpu_device(dev) else "cpu-xla",
    }
