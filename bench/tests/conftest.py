"""The benchmark's own tests run on the CPU: ``python -m pytest bench/tests``
from the repository's root."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
