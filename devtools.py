"""Dev helper: `import devtools` FIRST in ad-hoc scripts (before anything
imports jax) to pin the CPU backend with 8 virtual devices and full-f32
matmuls.  Mirrors tests/conftest.py."""
import os

if not os.environ.get("MXTPU_TEST_ON_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax as _jax

    _jax.config.update("jax_default_matmul_precision", "highest")
