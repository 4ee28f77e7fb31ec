"""Test config.

Every tier-1 run emulates an 8-device CPU platform (the XLA host-
platform device-count flag below, set BEFORE jax imports), so the
shard_map expert-parallel path (ep/executor.py, tests/test_ep.py) runs
real per-shard collectives in-process instead of being skipped on
single-device machines. CI sets the same flag at the job level.

Single-device semantics are unaffected: tests build meshes explicitly
(``make_ep_mesh`` / ``jax.make_mesh``) and nothing auto-shards over
the extra devices — code that doesn't ask for a mesh still runs on
device 0. launch/dryrun.py and the dry-run integration test spawn
subprocesses with their own XLA_FLAGS (512 emulated hosts) and are
likewise untouched.
"""
import os

_FLAG = "--xla_force_host_platform_device_count=8"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

import jax  # noqa: E402  (must follow the XLA_FLAGS export)

jax.config.update("jax_enable_x64", False)
