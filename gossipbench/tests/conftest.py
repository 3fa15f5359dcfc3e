"""The harness's own tests run on the CPU, never on the chip."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# eight virtual CPU devices, so a cell of four chips runs here
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8").strip()
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
