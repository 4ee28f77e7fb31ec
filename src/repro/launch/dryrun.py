"""Multi-pod dry-run: lower + compile every (arch x shape x mesh)
combination on the production mesh with ShapeDtypeStruct inputs (no
allocation), and record memory/cost/collective analyses for §Roofline.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape decode_32k [--multi-pod] [--out results.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all

The XLA_FLAGS line below MUST run before any other import that touches
jax: jax locks the device count on first backend init.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse        # noqa: E402
import json            # noqa: E402
import time            # noqa: E402
import traceback       # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import ArchConfig, ShapeConfig  # noqa: E402
from repro.configs.registry import assigned_names, get_config  # noqa: E402
from repro.configs.shapes import SHAPES, get_shape  # noqa: E402
from repro.launch import partition as PT  # noqa: E402
from repro.launch import roofline as RL  # noqa: E402
from repro.launch.inputs import (  # noqa: E402
    decode_cache_len, force_window_for, input_specs, policy_for)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.serve import make_prefill, make_serve_step  # noqa: E402
from repro.launch.train import make_train_step  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.optim.adamw import AdamWState  # noqa: E402
from repro.sharding import mesh_context  # noqa: E402

PARAM_DTYPE = jnp.bfloat16


def _params_sds(cfg: ArchConfig):
    return jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), PARAM_DTYPE))


def _opt_sds(params_sds):
    return AdamWState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        mu=jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
            params_sds),
        nu=jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
            params_sds))


def per_device_gb(sds_tree, spec_tree, mesh) -> float:
    """Exact per-device bytes of a sharded pytree (from its specs)."""
    total = 0.0
    flat_s, _ = jax.tree_util.tree_flatten(sds_tree)
    flat_p = jax.tree_util.tree_flatten(
        spec_tree, is_leaf=lambda x: isinstance(
            x, jax.sharding.PartitionSpec))[0]
    for sds, spec in zip(flat_s, flat_p):
        n = 1
        for d in sds.shape:
            n *= d
        shard = 1
        for entry in spec:
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                shard *= mesh.shape[a]
        total += n * sds.dtype.itemsize / shard
    return total / 1e9


def lower_one(cfg: ArchConfig, shape: ShapeConfig, mesh, *,
              policy=None, capacity_factor: float = 1.25,
              fsdp: bool = True, cache_dtype=None,
              disable_constraints=(),
              extra_tags: Optional[Dict] = None) -> Dict:
    """Lower + compile one combination; return the §Dry-run record."""
    n_dev = mesh.devices.size
    policy = policy if policy is not None else policy_for(cfg, shape)
    fw = force_window_for(cfg, shape) if shape.kind != "train" else None
    accum = 8 if (shape.kind == "train"
                  and RL.param_counts(cfg)["total"] > 50e9) else 1
    ba = PT.batch_axes(mesh, shape.global_batch)
    pspecs = PT.param_specs(cfg, mesh, _params_sds(cfg), fsdp=fsdp)
    ins = input_specs(cfg, shape, PARAM_DTYPE,
                      cache_dtype=cache_dtype)
    t0 = time.perf_counter()

    with mesh_context(mesh, ba, disable=disable_constraints):
        if shape.kind == "train":
            # >50B-param models microbatch 8x to fit activations in HBM
            fn = make_train_step(cfg, policy=policy, remat=True,
                                 capacity_factor=capacity_factor,
                                 accum_steps=accum)
            ospecs = PT.opt_specs(pspecs)
            tspec = PT.token_spec(cfg, mesh, shape.global_batch)
            in_shardings = [pspecs, ospecs, tspec]
            args = [_params_sds(cfg), _opt_sds(_params_sds(cfg)),
                    ins["tokens"]]
            if "prefix_embeds" in ins:
                in_shardings.append(
                    PT.prefix_spec(cfg, mesh, shape.global_batch))
                args.append(ins["prefix_embeds"])
            jitted = jax.jit(
                fn,
                in_shardings=tuple(PT.named(mesh, s) for s in in_shardings),
                out_shardings=(PT.named(mesh, pspecs),
                               PT.named(mesh, ospecs), None),
                donate_argnums=(0, 1))
        elif shape.kind == "prefill":
            fn = make_prefill(cfg, cache_len=shape.seq_len + 512,
                              force_window=fw,
                              capacity_factor=capacity_factor)
            cspecs = PT.cache_specs(cfg, mesh, shape.global_batch)
            tspec = PT.token_spec(cfg, mesh, shape.global_batch)
            in_shardings = [pspecs, tspec]
            args = [_params_sds(cfg), ins["tokens"]]
            if "prefix_embeds" in ins:
                in_shardings.append(
                    PT.prefix_spec(cfg, mesh, shape.global_batch))
                args.append(ins["prefix_embeds"])
            lspec = PT.logits_spec(cfg, mesh, shape.global_batch,
                                   with_seq=False)
            jitted = jax.jit(
                fn,
                in_shardings=tuple(PT.named(mesh, s) for s in in_shardings),
                out_shardings=(PT.named(mesh, lspec),
                               PT.named(mesh, cspecs), None))
        else:  # decode
            fn = make_serve_step(cfg, policy=policy, force_window=fw,
                                 capacity_factor=capacity_factor)
            cspecs = PT.cache_specs(cfg, mesh, shape.global_batch)
            tspec = PT.token_spec(cfg, mesh, shape.global_batch)
            lspec = PT.logits_spec(cfg, mesh, shape.global_batch,
                                   with_seq=True)
            jitted = jax.jit(
                fn,
                in_shardings=(PT.named(mesh, pspecs),
                              PT.named(mesh, tspec),
                              PT.named(mesh, cspecs)),
                out_shardings=(PT.named(mesh, lspec),
                               PT.named(mesh, cspecs), None),
                donate_argnums=(2,))
            args = [_params_sds(cfg), ins["tokens"], ins["cache"]]

        lowered = jitted.lower(*args)
        compiled = lowered.compile()

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    hlo_text = compiled.as_text()
    coll = RL.collective_bytes(hlo_text)
    coll_split = RL.collective_bytes_split(hlo_text)
    # exact per-device state footprints from the sharding specs — the
    # TPU-native numbers (XLA-CPU float-normalization duplicates bf16
    # loop-carried state in f32, inflating peak_hbm_gb; see
    # EXPERIMENTS.md §Dry-run notes)
    analytic = {"params_gb": per_device_gb(_params_sds(cfg), pspecs, mesh)}
    if shape.kind == "train":
        analytic["opt_gb"] = 2.0 * per_device_gb(
            jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                _params_sds(cfg)), pspecs, mesh)
    if shape.kind == "decode":
        cspecs_flat = PT.cache_specs(cfg, mesh, shape.global_batch)
        analytic["cache_gb"] = per_device_gb(ins["cache"], cspecs_flat,
                                             mesh)
    rec = {
        "arch": cfg.name,
        "shape": shape.name,
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "devices": int(n_dev),
        "policy": policy.mode,
        "flops_per_device": float(ca.get("flops", 0.0)),
        "bytes_per_device": float(ca.get("bytes accessed", 0.0)),
        "collective_bytes_per_device": float(sum(coll.values())),
        "collective_bytes_inside_loop": int(coll_split["inside"]),
        "collective_bytes_outside_loop": int(coll_split["outside"]),
        "collectives": {k: int(v) for k, v in coll.items() if v},
        "argument_bytes_per_device": int(ma.argument_size_in_bytes),
        "output_bytes_per_device": int(ma.output_size_in_bytes),
        "temp_bytes_per_device": int(ma.temp_size_in_bytes),
        "alias_bytes_per_device": int(ma.alias_size_in_bytes),
        "peak_hbm_gb": (ma.argument_size_in_bytes
                        + ma.output_size_in_bytes
                        + ma.temp_size_in_bytes
                        - ma.alias_size_in_bytes) / 1e9,
        "compile_s": round(time.perf_counter() - t0, 1),
        "analytic": {k: round(v, 3) for k, v in analytic.items()},
    }
    cbe = 1 if (cache_dtype is not None
                and jnp.dtype(cache_dtype).itemsize == 1) else 2
    rec.update(RL.step_terms(rec, n_dev, cfg, shape, window=fw,
                             accum=accum, policy=policy,
                             cache_bytes_per_el=cbe))
    if extra_tags:
        rec.update(extra_tags)
    return rec


def run(arch: str, shape_name: str, multi_pod: bool, out: Optional[str],
        capacity_factor: float = 1.25) -> Dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = lower_one(cfg, shape, mesh, capacity_factor=capacity_factor)
    line = (f"{rec['arch']:22s} {rec['shape']:12s} mesh={rec['mesh']:8s} "
            f"peak={rec['peak_hbm_gb']:.2f}GB "
            f"flops/dev={rec['flops_per_device']:.3e} "
            f"coll/dev={rec['collective_bytes_per_device']:.3e} "
            f"dom={rec['dominant']}")
    print(line, flush=True)
    if out:
        existing = []
        if os.path.exists(out):
            existing = json.load(open(out))
        existing = [r for r in existing
                    if not (r["arch"] == rec["arch"]
                            and r["shape"] == rec["shape"]
                            and r["mesh"] == rec["mesh"])]
        existing.append(rec)
        json.dump(existing, open(out, "w"), indent=1)
    return rec


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    args = ap.parse_args(argv)

    if args.all:
        fails = []
        for arch in assigned_names():
            for shape in SHAPES:
                for mp in (False, True):
                    try:
                        run(arch, shape, mp, args.out)
                    except Exception as e:  # noqa: BLE001
                        fails.append((arch, shape, mp, repr(e)))
                        print(f"FAIL {arch} {shape} multi={mp}: {e}",
                              flush=True)
                        traceback.print_exc()
        print(f"\n{len(fails)} failures")
        raise SystemExit(1 if fails else 0)
    run(args.arch, args.shape, args.multi_pod, args.out)


if __name__ == "__main__":
    main()
