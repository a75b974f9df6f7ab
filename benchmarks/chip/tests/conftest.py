"""The benchmark's own tests run on the CPU, at the registry's reduced
sizes, with JAX's persistent compilation cache off."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
