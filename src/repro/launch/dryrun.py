import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST precede any other import (jax locks the device count
on first init).  For each cell we jit the train/prefill/serve step with
ShapeDtypeStruct inputs and the production shardings, compile, record
memory_analysis / cost_analysis, parse collective bytes from the HLO, and
derive the roofline terms (§Roofline).

Usage:
    python -m repro.launch.dryrun --arch qwen3_8b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--out results.jsonl]
"""
import argparse
import json
import sys
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import RunConfig, all_cells, get_config, get_shape
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyze, model_flops_for
from repro.launch.specs import decode_input_specs, train_input_specs
from repro.models.base import ShardCtx, tree_specs_to_shapes
from repro.models.lm import forward, lm_loss, model_spec
from repro.train.optimizer import AdamWConfig
from repro.train.trainstep import make_train_step, train_state_specs


def _named(mesh, tree_specs):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def dryrun_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    verbose: bool = True,
    remat: str = "full",
    probe: bool = True,
    microbatch: int = 0,          # §Perf knob: grad-accumulation microbatch
    capacity_factor: float = 0.0,  # §Perf knob: MoE capacity override
    serve_fsdp: bool = False,      # §Perf knob: keep FSDP params for decode
    tag: str = "",
):
    import dataclasses as _dc

    cfg = get_config(arch)
    if capacity_factor and cfg.moe is not None:
        cfg = _dc.replace(
            cfg, moe=_dc.replace(cfg.moe, capacity_factor=capacity_factor)
        )
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    pods = 2 if multi_pod else 1
    ctx = ShardCtx(
        tp=16, dp=16, pods=pods,
        data_axes=("pod", "data") if multi_pod else ("data",),
    )
    run = RunConfig(
        model=cfg, shape=shape, dp=16, tp=16, pods=pods, remat=remat,
        microbatch=microbatch or None,
    )

    # Serving steps have no optimizer state: FSDP(ZeRO) sharding of params
    # over the data axes would force a full param all-gather per decoded
    # token.  Default for decode cells: params sharded over model only
    # (replicated across data) — the §Perf fix for collective-bound decode.
    ctx_params = ctx
    if shape.kind == "decode" and not serve_fsdp:
        ctx_params = ShardCtx(
            tp=ctx.tp, dp=1, pods=1, data_axes=ctx.data_axes
        )
    (p_shapes, p_specs), (o_shapes, o_specs) = train_state_specs(
        cfg, run, ctx_params
    )

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            in_shapes, in_specs = train_input_specs(cfg, shape, ctx)
            step_fn, _ = make_train_step(cfg, run, mesh=mesh, use_ep=True)
            jitted = jax.jit(
                step_fn,
                in_shardings=(
                    _named(mesh, p_specs),
                    _named(mesh, o_specs),
                    _named(mesh, in_specs),
                ),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(p_shapes, o_shapes, in_shapes)
        elif shape.kind == "prefill":
            in_shapes, in_specs = train_input_specs(cfg, shape, ctx)

            def prefill_step(params, batch):
                logits, _, _ = forward(
                    params, cfg, batch["tokens"], ctx, mesh=mesh,
                    vis_embeds=batch.get("vis_embeds"), remat=(remat != "none"),
                    use_ep=True,
                )
                return logits[:, -1]

            jitted = jax.jit(
                prefill_step,
                in_shardings=(_named(mesh, p_specs), _named(mesh, in_specs)),
            )
            lowered = jitted.lower(p_shapes, in_shapes)
        else:  # decode
            in_shapes, in_specs = decode_input_specs(cfg, shape, ctx)

            def serve_step(params, cache, tokens, pos):
                logits, new_cache, _ = forward(
                    params, cfg, tokens, ctx, mesh=mesh, cache=cache,
                    start_pos=pos, use_ep=True,
                )
                return logits[:, -1], new_cache

            jitted = jax.jit(
                serve_step,
                in_shardings=(
                    _named(mesh, p_specs),
                    _named(mesh, in_specs["cache"]),
                    _named(mesh, in_specs["tokens"]),
                    NamedSharding(mesh, in_specs["pos"]),
                ),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(
                p_shapes, in_shapes["cache"], in_shapes["tokens"],
                in_shapes["pos"],
            )
        compiled = lowered.compile()
    dt = time.time() - t0

    hlo = compiled.as_text()
    report = analyze(
        arch, shape_name, mesh_name, chips, compiled, hlo,
        model_flops_for(cfg, shape),
    )
    raw_flops = report.flops_per_device
    if probe:
        # loop-exact correction: HLO cost analysis counts while bodies once
        # (launch/probe.py) — replace flops/bytes/collectives with the summed
        # loop-free probe compiles.
        from repro.launch.probe import corrected_costs

        total, detail = corrected_costs(
            cfg, run, ctx, mesh, shape.kind, ctx_params=ctx_params
        )
        report.flops_per_device = total.flops
        report.bytes_per_device = total.bytes
        report.collective_bytes_per_device = float(sum(total.coll.values()))
        report.collective_by_kind = total.coll
    row = report.row()
    if tag:
        row["tag"] = tag
    row["raw_scan_flops_per_dev"] = raw_flops
    row["compile_s"] = round(dt, 1)
    ma = compiled.memory_analysis()
    row["arg_gb"] = round(ma.argument_size_in_bytes / 2**30, 3)
    row["temp_gb"] = round(ma.temp_size_in_bytes / 2**30, 3)
    row["out_gb"] = round(ma.output_size_in_bytes / 2**30, 3)
    if verbose:
        print(json.dumps(row))
        print(f"memory_analysis: {ma}", file=sys.stderr)
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--remat", default="full", choices=["full", "none"])
    ap.add_argument("--no-probe", action="store_true",
                    help="skip roofline probes (compile-success check only)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--capacity-factor", type=float, default=0.0)
    ap.add_argument("--serve-fsdp", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--worker", default=None,
                    help="i/n: run cell subset i of n (parallel sweeps)")
    args = ap.parse_args()

    cells = all_cells() if args.all else [(args.arch, args.shape)]
    if args.worker:
        i, n = (int(x) for x in args.worker.split("/"))
        cells = [c for j, c in enumerate(cells) if j % n == i]
    out_f = open(args.out, "a") if args.out else None
    failures = 0
    for arch, shape in cells:
        try:
            row = dryrun_cell(
                arch, shape, multi_pod=args.multi_pod, probe=not args.no_probe,
                microbatch=args.microbatch, capacity_factor=args.capacity_factor,
                serve_fsdp=args.serve_fsdp, remat=args.remat, tag=args.tag,
            )
            if out_f:
                out_f.write(json.dumps(row) + "\n")
                out_f.flush()
        except Exception:
            failures += 1
            print(f"FAILED {arch} {shape}", file=sys.stderr)
            traceback.print_exc()
    if out_f:
        out_f.close()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
