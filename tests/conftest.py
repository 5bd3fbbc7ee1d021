"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths compile
and execute without TPU hardware (the driver separately dry-runs the real
multi-chip path via ``__graft_entry__.dryrun_multichip``).  Env vars must be
set before the first ``import jax`` anywhere in the test process.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
