import os

# Tests must see exactly ONE CPU device (the 512-device override belongs to
# launch/dryrun.py only). Also keep compilation deterministic.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from hypothesis import HealthCheck, settings  # noqa: E402

settings.register_profile(
    "repro", deadline=None, max_examples=30,
    suppress_health_check=[HealthCheck.too_slow,
                           HealthCheck.data_too_large])
settings.load_profile("repro")
