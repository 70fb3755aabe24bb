"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be the process entrypoint (``python -m repro.launch.dryrun``): the
first two lines below claim 512 placeholder CPU devices before any jax
import so ``jax.make_mesh`` can build the production meshes. Nothing else
in the repo sets this flag — smoke tests and benches see 1 device.

Per cell this produces:
  * the full-depth compile (scan-over-layers) — the *fit + shard proof*:
    ``compiled.memory_analysis()`` (bytes/device) and the collective
    schedule from the post-SPMD HLO;
  * two unrolled probes (L=1, L=3) — ``cost_analysis()`` FLOPs/bytes and
    per-collective bytes decompose linearly in L (layers are identical),
    giving exact full-depth roofline terms (see analysis/roofline.py).

Artifacts are JSON files under ``--out``, read by
:mod:`repro.analysis.roofline`.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512"
    # XLA:CPU-only pass that widens small-dtype all-reduces; it (a)
    # CHECK-crashes on the compressed-gradient program and (b) would
    # distort the counted collective byte widths. TPU is unaffected.
    " --xla_disable_hlo_passes=all-reduce-promotion"
)

# ruff: noqa: E402  (the two lines above must precede all other imports)
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.analysis import hlo as hlo_analysis
from repro.analysis import roofline
from repro.config import (
    MULTI_POD, SINGLE_POD, MeshConfig, ModelConfig, RunConfig, ShapeConfig,
    SHAPES, applicable_shapes,
)
from repro.core import plan as plan_mod
from repro.distributed.sharding import Rules, make_rules, make_shard_fn, named
from repro.launch.mesh import make_mesh_from_config
from repro.models import api as model_api
from repro.models.layers import LayerCtx
from repro.training.train_state import TrainState, make_train_step


# ---------------------------------------------------------------------------
# Step builders — one per shape kind
# ---------------------------------------------------------------------------


def _ctx(cfg: ModelConfig, mesh, rules, run: RunConfig) -> LayerCtx:
    groups = 1
    if rules is not None:
        sizes = rules.axis_sizes
        for a in rules.batch_axes:
            groups *= sizes[a]
    base = run.plan if run.plan is not None else plan_mod.make_plan()
    ep = base.with_overrides(
        backend="xla",     # Mosaic doesn't lower on CPU
        fallback=False,    # no cond double-count in cost analysis
        # pre-T1 baseline (Fig. 4(b)): synchronized softmax everywhere
        scheme="sync" if run.sync_softmax else None,
    )
    return LayerCtx(
        cfg=cfg,
        shard=make_shard_fn(mesh, rules),
        plan=ep,
        moe_groups=groups,
        mesh=mesh if run.grad_compression == "none" else None,
        rules=rules,
    )


def build_train(cfg: ModelConfig, shape: ShapeConfig, mesh, rules: Rules,
                run: RunConfig, *, unroll: bool):
    api = model_api.get_model(cfg)
    ctx = _ctx(cfg, mesh, rules, run)
    step = make_train_step(api, ctx, run, unroll=unroll, mesh=mesh)

    state_struct = jax.eval_shape(
        lambda: TrainState.create(
            api.init_params(jax.random.PRNGKey(0)),
            npods=rules.axis_sizes.get("pod", 0) if rules else 0,
            compression=run.grad_compression,
        )
    )
    batch_struct = model_api.train_input_specs(cfg, shape)
    if mesh is None:
        return jax.jit(step, donate_argnums=(0,)), (state_struct,
                                                    batch_struct)
    pspec = rules.param_spec_tree(state_struct.params)
    ef_spec = None
    if state_struct.ef_err is not None:
        ef_spec = jax.tree.map(lambda _: P("pod"), state_struct.ef_err)
    state_spec = TrainState(
        step=P(), params=pspec, m=pspec, v=pspec, ef_err=ef_spec)
    batch_spec = rules.input_specs_tree(batch_struct)

    in_shardings = (named(mesh, state_spec), named(mesh, batch_spec))
    out_shardings = (named(mesh, state_spec), None)
    fn = jax.jit(step, in_shardings=in_shardings, out_shardings=out_shardings,
                 donate_argnums=(0,))   # state updated in place
    return fn, (state_struct, batch_struct)


def build_decode(cfg: ModelConfig, shape: ShapeConfig, mesh, rules: Rules,
                 run: RunConfig, *, unroll: bool):
    api = model_api.get_model(cfg)
    ctx = _ctx(cfg, mesh, rules, run)

    def serve_step(params, tokens, cache, lengths):
        logits, new_cache = api.decode_step(
            ctx, params, tokens, cache, lengths, unroll=unroll)
        return logits, new_cache

    params_struct = jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0)))
    pspec = rules.param_spec_tree(params_struct) if rules else None
    specs = model_api.serve_decode_input_specs(cfg, shape)
    cache_struct = specs["cache"]
    if mesh is None:
        fn = jax.jit(serve_step, donate_argnums=(2,))
        return fn, (params_struct, specs["tokens"], cache_struct,
                    specs["lengths"])
    cache_spec = jax.tree.map(
        lambda l: rules.cache_spec(l.shape), cache_struct)
    tok_spec = rules.batch_spec(specs["tokens"].shape)
    len_spec = rules.batch_spec(specs["lengths"].shape)

    in_shardings = (
        named(mesh, pspec), NamedSharding(mesh, tok_spec),
        named(mesh, cache_spec), NamedSharding(mesh, len_spec),
    )
    # pin the cache output to its input layout: no per-token resharding;
    # donate it: the KV append must be in place (32k cache per token!)
    out_shardings = (None, named(mesh, cache_spec))
    fn = jax.jit(serve_step, in_shardings=in_shardings,
                 out_shardings=out_shardings, donate_argnums=(2,))
    args = (params_struct, specs["tokens"], cache_struct, specs["lengths"])
    return fn, args


def build_prefill(cfg: ModelConfig, shape: ShapeConfig, mesh, rules: Rules,
                  run: RunConfig, *, unroll: bool):
    api = model_api.get_model(cfg)
    ctx = _ctx(cfg, mesh, rules, run)
    specs = model_api.serve_prefill_input_specs(cfg, shape)
    cache_struct = api.cache_spec(
        model_api.DenseLayout(shape.global_batch, shape.seq_len))

    def prefill_step(params, tokens, lengths, extra):
        logits, cache = api.prefill(
            ctx, params, tokens, lengths, cache_struct,
            unroll=unroll, **extra)
        return logits, cache

    params_struct = jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0)))
    extra = {k: v for k, v in specs.items()
             if k not in ("tokens", "lengths")}
    if mesh is None:
        return jax.jit(prefill_step), (params_struct, specs["tokens"],
                                       specs["lengths"], extra)
    pspec = rules.param_spec_tree(params_struct)
    extra_spec = {k: rules.batch_spec(v.shape) for k, v in extra.items()}
    in_shardings = (
        named(mesh, pspec),
        NamedSharding(mesh, rules.batch_spec(specs["tokens"].shape)),
        NamedSharding(mesh, rules.batch_spec(specs["lengths"].shape)),
        named(mesh, extra_spec),
    )
    cache_spec = jax.tree.map(
        lambda l: rules.cache_spec(l.shape), cache_struct)
    out_shardings = (None, named(mesh, cache_spec))
    fn = jax.jit(prefill_step, in_shardings=in_shardings,
                 out_shardings=out_shardings)
    args = (params_struct, specs["tokens"], specs["lengths"], extra)
    return fn, args


BUILDERS = {"train": build_train, "prefill": build_prefill,
            "decode": build_decode}

# serving keeps params data-replicated below this per-chip TP-shard size
# (v5e: 16 GB HBM - KV cache - activations headroom)
SERVE_REPLICATE_BUDGET_BYTES = 10e9


# ---------------------------------------------------------------------------
# Lower + compile + analyse one cell
# ---------------------------------------------------------------------------


def _probe_cfg(cfg: ModelConfig, num_layers: int) -> ModelConfig:
    updates: dict[str, Any] = {"num_layers": num_layers}
    if cfg.encoder_layers:
        updates["encoder_layers"] = num_layers
    return dataclasses.replace(cfg, **updates)


def lower_cell(
    cfg: ModelConfig,
    shape: ShapeConfig,
    mesh_cfg: MeshConfig,
    mesh,
    run: RunConfig,
    *,
    unroll: bool = False,
    compile_: bool = True,
):
    """Returns ((lowered, compiled|None), seconds_lower, seconds_compile).

    ``mesh=None`` lowers the unsharded program (used to count exact global
    FLOPs/bytes: inside-shard_map ops are otherwise reported per shard).
    """
    # Serving layout: params replicate over `data` when the TP shard fits
    # the HBM budget — FSDP would all-gather the whole parameter set every
    # decoded token (EXPERIMENTS.md §Perf, deepseek decode iteration 2).
    # Training always uses FSDP (optimizer state triples the footprint).
    fsdp_params = True
    if shape.kind in ("decode", "prefill"):
        model_shards = dict(zip(mesh_cfg.axis_names,
                                mesh_cfg.shape)).get("model", 1)
        tp_bytes = cfg.param_count() * 2 / model_shards
        fsdp_params = tp_bytes > SERVE_REPLICATE_BUDGET_BYTES
    rules = None if mesh is None else make_rules(
        mesh_cfg,
        seq_shard_kv=run.seq_shard_attention,
        fsdp_over_pod=run.grad_compression == "none",
        act_over_pod=run.grad_compression == "none",
        fsdp_params=fsdp_params,
    )
    fn, args = BUILDERS[shape.kind](cfg, shape, mesh, rules, run,
                                    unroll=unroll)
    t0 = time.time()
    lowered = fn.lower(*args)
    t1 = time.time()
    if not compile_:
        return (lowered, None), t1 - t0, 0.0
    compiled = lowered.compile()
    t2 = time.time()
    return (lowered, compiled), t1 - t0, t2 - t1


def analyse(lowered, compiled) -> dict:
    """FLOPs/bytes from the *pre-SPMD* module (global, exact, independent
    of per-L partitioning strategy — compiled per-device cost_analysis on
    XLA:CPU also misses dots inside wrapped fusions); collective schedule
    and memory fit from the *post-SPMD* compiled module."""
    out: dict[str, Any] = {}
    try:
        cost = lowered.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        out["flops_global"] = float(cost.get("flops", 0.0))
        out["bytes_global"] = float(cost.get("bytes accessed", 0.0))
    except Exception as e:  # noqa: BLE001
        out["lowered_cost_error"] = repr(e)
    if compiled is None:
        return out
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        out["flops_per_device"] = float(cost.get("flops", 0.0))
        out["bytes_per_device"] = float(cost.get("bytes accessed", 0.0))
    except Exception as e:  # noqa: BLE001
        out["cost_error"] = repr(e)
    try:
        mem = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                out[k] = int(v)
        if "argument_size_in_bytes" in out:
            out["per_device_bytes"] = (
                out["argument_size_in_bytes"]
                + out.get("temp_size_in_bytes", 0)
                + out.get("output_size_in_bytes", 0)
                - out.get("alias_size_in_bytes", 0)
            )
    except Exception as e:  # noqa: BLE001
        out["memory_error"] = repr(e)
    try:
        text = compiled.as_text()
        stats = hlo_analysis.parse_collectives(text)
        out["collective_bytes"] = stats.total_bytes()
        out["collective_counts"] = stats.counts
        out["collective_by_kind"] = stats.by_kind()
    except Exception as e:  # noqa: BLE001
        out["hlo_error"] = repr(e)
    return out


def run_cell(
    arch: str,
    shape_name: str,
    mesh_cfg: MeshConfig,
    mesh,
    run: RunConfig,
    *,
    probes: tuple[int, ...] = (1, 3),
    full: bool = True,
    sync_softmax: bool = False,
    plan: Optional[plan_mod.ExecutionPlan] = None,
) -> dict:
    cfg = configs.get(arch)
    if plan is not None:
        run = dataclasses.replace(run, plan=plan)
    if sync_softmax:   # paper-faithful pre-T1 baseline (Fig. 4(b))
        run = dataclasses.replace(run, sync_softmax=True)
    shape = SHAPES[shape_name]
    mesh_name = "x".join(str(s) for s in mesh_cfg.shape)
    if sync_softmax:
        mesh_name += "-sync"
    record: dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "chips": mesh_cfg.num_devices, "ok": False,
    }
    try:
        if full:
            (lowered, compiled), tl, tc = lower_cell(
                cfg, shape, mesh_cfg, mesh, run, unroll=False)
            record["full"] = analyse(lowered, compiled)
            record["full"]["lower_s"] = round(tl, 2)
            record["full"]["compile_s"] = round(tc, 2)
            del lowered, compiled
        probe_rows = []
        for nl in probes:
            pcfg = _probe_cfg(cfg, nl)
            (lowered, compiled), tl, tc = lower_cell(
                pcfg, shape, mesh_cfg, mesh, run, unroll=True)
            row = analyse(lowered, compiled)
            # exact global FLOPs/bytes: unsharded lowering (shard_map
            # regions in the sharded module are counted per shard)
            (lone, _), _, _ = lower_cell(
                pcfg, shape, mesh_cfg, None, run, unroll=True,
                compile_=False)
            gcost = lone.cost_analysis()
            row["flops_global"] = float(gcost.get("flops", 0.0))
            row["bytes_global"] = float(gcost.get("bytes accessed", 0.0))
            row["num_layers"] = nl
            row["lower_s"] = round(tl, 2)
            row["compile_s"] = round(tc, 2)
            probe_rows.append(row)
            del lowered, compiled, lone
        record["probes"] = probe_rows
        record["ok"] = True
    except Exception:  # noqa: BLE001
        record["error"] = traceback.format_exc(limit=20)
    return record


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def iter_cells(arch_filter=None, shape_filter=None):
    for arch in configs.ASSIGNED:
        if arch_filter and arch != arch_filter:
            continue
        cfg = configs.get(arch)
        for shape in applicable_shapes(cfg):
            if shape_filter and shape.name != shape_filter:
                continue
            yield arch, shape.name


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--no-probes", action="store_true",
                    help="full compile only (multi-pod shard proof)")
    ap.add_argument("--no-full", action="store_true",
                    help="probes only (roofline terms)")
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8_ef"])
    ap.add_argument("--sync-softmax", action="store_true",
                    help="paper-faithful pre-T1 baseline: dispatch every "
                         "attention op with the synchronized scheme")
    ap.add_argument("--plan", default=None, metavar="PATH",
                    help="ExecutionPlan JSON to dispatch by (requires "
                         "--arch: a plan's provenance pins one config)")
    ap.add_argument("--tune", action="store_true",
                    help="tune a fresh plan per arch before lowering "
                         "(analytical backend; backend/fallback are still "
                         "forced to xla/off for cost-analysis hygiene)")
    ap.add_argument("--weight-dtype",
                    choices=["bf16", "int8", "fp8"], default=None,
                    help="override the plan's GEMM weight storage dtype "
                         "(matmul.weight_dtype) for cost analysis — the "
                         "lowered cells carry the knob so the roofline "
                         "sees the quantized weight stream")
    args = ap.parse_args()
    if args.plan and not args.arch:
        ap.error("--plan requires --arch (plan provenance pins one config)")

    os.makedirs(args.out, exist_ok=True)
    run = RunConfig(grad_compression=args.grad_compression)
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(SINGLE_POD)
    if args.mesh in ("multi", "both"):
        meshes.append(MULTI_POD)

    # resolve plans once per arch (a tune sweep / file parse per cell
    # would be pure waste — cells only vary shape and mesh)
    plans: dict[str, plan_mod.ExecutionPlan] = {}

    def plan_for(arch: str) -> Optional[plan_mod.ExecutionPlan]:
        if not (args.tune or args.plan or args.weight_dtype):
            return None
        if arch not in plans:
            cfg = configs.get(arch)
            if args.tune:
                tuned = plan_mod.tune(cfg)
                if args.plan:   # serve.py semantics: tune + save to --plan
                    tuned.save(args.plan)
                base = tuned
            elif args.plan:
                base = plan_mod.ExecutionPlan.load(args.plan, cfg=cfg)
            else:
                base = plan_mod.make_plan()
            if args.weight_dtype is not None:
                base = dataclasses.replace(
                    base, matmul=dataclasses.replace(
                        base.matmul, weight_dtype=args.weight_dtype))
            plans[arch] = base
        return plans[arch]

    failures = 0
    for mesh_cfg in meshes:
        mesh = make_mesh_from_config(mesh_cfg)
        mesh_name = "x".join(str(s) for s in mesh_cfg.shape)
        # probes (roofline) are single-pod only per the assignment;
        # multi-pod is the shard proof (full compile).
        probes = () if (args.no_probes or mesh_cfg is MULTI_POD) else (1, 3)
        for arch, shape_name in iter_cells(args.arch, args.shape):
            t0 = time.time()
            rec = run_cell(arch, shape_name, mesh_cfg, mesh, run,
                           probes=probes, full=not args.no_full,
                           sync_softmax=args.sync_softmax,
                           plan=plan_for(arch))
            dt = time.time() - t0
            tag = "OK " if rec["ok"] else "FAIL"
            print(f"[{tag}] {mesh_name:<9} {arch:<16} {shape_name:<12} "
                  f"({dt:.1f}s)", flush=True)
            if not rec["ok"]:
                failures += 1
                print(rec["error"].splitlines()[-1])
            fname = f"{arch}__{shape_name}__{mesh_name}.json"
            with open(os.path.join(args.out, fname), "w") as f:
                json.dump(rec, f, indent=2)
    print(f"done; failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
