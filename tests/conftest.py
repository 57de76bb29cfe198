import os

# All tests run on a virtual 8-device CPU mesh so multi-chip sharding paths
# compile and execute without TPU hardware; chip_smoke.py is the run on the
# real chip.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import hashlib

import pytest


@pytest.fixture(autouse=True)
def fresh_graph():
    """Each test builds its own dataflow graph."""
    from pathway_tpu.internals.graph import G

    G.clear()
    yield
    G.clear()


@pytest.fixture(autouse=True)
def _fault_isolation():
    """No fault plan leaks across tests; re-arm any env-requested plan."""
    from pathway_tpu.testing import faults

    yield
    faults.reset()
    faults.configure_from_env()
    from pathway_tpu.internals.errors import clear_dead_letter_sinks

    clear_dead_letter_sinks()


@pytest.fixture
def chaos_seed(request):
    """Deterministic fault-injection seed for @pytest.mark.chaos tests.

    Defaults to a stable hash of the test's nodeid so every test gets its
    own (but reproducible) fault sequence; ``PATHWAY_FAULT_SEED`` in the
    environment overrides it globally.  The seed is printed, so a chaos
    failure reproduces with
    ``PATHWAY_FAULT_SEED=<printed> pytest <nodeid>``.
    """
    env = os.environ.get("PATHWAY_FAULT_SEED")
    if env:
        seed = int(env)
    else:
        digest = hashlib.blake2b(
            request.node.nodeid.encode(), digest_size=4
        ).digest()
        seed = int.from_bytes(digest, "little")
    print(f"[chaos] PATHWAY_FAULT_SEED={seed}")
    return seed
