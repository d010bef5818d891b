"""Test harness: force an 8-device virtual CPU mesh so multi-chip sharding paths run
without TPU hardware (the driver separately dry-runs `__graft_entry__.dryrun_multichip`)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# CPU test entries must never fill the checkout's persistent compile cache
# (utils/devices.enable_compilation_cache), which the driver copies. The env
# var also reaches the CLI/tool subprocesses the tests start.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

from open_simulator_tpu.utils.devices import force_cpu_platform, request_cpu_devices

request_cpu_devices(8)
force_cpu_platform()

# The 8 virtual devices would auto-enable the engine's mesh path for every
# test (Simulator._resolve_mesh); keep the default suite single-device and let
# the parallel/mesh tests opt in with use_mesh=True.
os.environ.setdefault("OPEN_SIMULATOR_MESH", "0")
