import os

# Tests run on the single real CPU device (NOT the 512-device dry-run world);
# keep compilation deterministic and quiet. Pallas kernels follow the backend
# (kernels/backend.resolve_interpret): on the CPU they run through the Pallas
# interpreter, so tier-1 exercises the real kernel bodies -- the same code
# that compiles to Mosaic on a TPU.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_cpu_multi_thread_eigen=false")

import jax

jax.config.update("jax_enable_x64", False)

# Hypothesis profiles (no-op when hypothesis is not installed). Tier-1 / CI
# run the pinned deterministic "ci" profile (derandomized, 500 examples) via
# HYPOTHESIS_PROFILE=ci; plain local runs get a quicker derandomized "dev"
# profile. The genuinely random deep fuzz lives behind `pytest -m slow`.
try:
    from hypothesis import settings

    settings.register_profile("ci", max_examples=500, deadline=None,
                              derandomize=True, print_blob=True)
    settings.register_profile("dev", max_examples=100, deadline=None,
                              derandomize=True)
    settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "dev"))
except ImportError:
    pass
