import os

# smoke tests and benches see ONE device; only launch/dryrun.py forces 512
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# no persistent compile cache in tests, nor in the worker processes they
# spawn (`python -m repro.frontend.worker` turns it on for real runs)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
