"""Test harness: force JAX onto 8 virtual CPU devices before first import.

Multi-chip hardware is not available in CI; sharding logic is validated on a
virtual CPU mesh (the fake-backend story the reference lacked — SURVEY §4).
The actual forcing lives in ``kubeshare_tpu.utils.virtualcpu`` (shared with
the driver entry ``__graft_entry__.dryrun_multichip``); that module imports
no jax at module scope, so it is safe to call pre-initialization here.
"""

from kubeshare_tpu.utils.virtualcpu import force_virtual_cpu

if not force_virtual_cpu(8):  # not an assert: -O must not skip the forcing
    raise RuntimeError("jax initialized before conftest could force CPU")

# Subprocesses spawned by tests (workloads, proxies, rendezvous ranks)
# inherit os.environ, so they are CPU-only by the forcing above
# (JAX_PLATFORMS=cpu).
