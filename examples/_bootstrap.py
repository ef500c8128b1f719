"""Shared example bootstrap: repo root on sys.path and an 8-device virtual
CPU mesh for the distributed demos. The platform is chosen the plain way:
``JAX_PLATFORMS=cpu python examples/foo.py`` runs on the CPU, and without
the variable JAX takes the accelerator it finds.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# before any jax import: virtual host devices for the mesh examples (only
# affects the CPU platform; harmless on real TPU backends)
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()
