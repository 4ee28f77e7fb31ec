"""Small-mesh dry-run integration: the full partition-rule + lowering
pipeline on an 8-host-device (2x4) mesh, run in a SUBPROCESS so the
forced device count never leaks into other tests."""
import json
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.configs.registry import get_config
    from repro.configs.base import ShapeConfig
    from repro.launch.dryrun import lower_one
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    results = {}
    for arch, fam in [("granite-moe-1b-a400m", "moe"),
                      ("mamba2-370m", "ssm"),
                      ("zamba2-1.2b", "hybrid"),
                      ("musicgen-large", "audio")]:
        cfg = get_config(arch).reduced(num_layers=2, max_d_model=256)
        # tiny shapes, mesh-divisible
        train = ShapeConfig(name="train_4k", seq_len=64, global_batch=4,
                            kind="train")
        decode = ShapeConfig(name="decode_32k", seq_len=64, global_batch=4,
                             kind="decode", cache_len=64)
        for shape in (train, decode):
            rec = lower_one(cfg, shape, mesh)
            results[f"{arch}:{shape.kind}"] = dict(
                flops=rec["flops_per_device"],
                coll=rec["collective_bytes_per_device"],
                dom=rec["dominant"])
    print("RESULT " + json.dumps(results))
""")


@pytest.mark.slow
def test_small_mesh_dryrun_all_families():
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=1200, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                           "HOME": "/root"})
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    results = json.loads(line[len("RESULT "):])
    assert len(results) == 8
    for k, v in results.items():
        assert v["flops"] > 0, k
        # every distributed combo must actually communicate
        assert v["coll"] > 0, k
